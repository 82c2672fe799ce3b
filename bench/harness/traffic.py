"""The one traffic generator: a mix file's parameters -> a schedule.

A mix (`bench/traffic/<name>.json`) is data.  It states

  tables      every table the run loads (the configuration's names)
  batch       the serving loop's batch cap
  tenant_queue_cap
              (optional) the most requests of one tenant the loop holds
              queued before it refuses more; absent: the loop's default
  streams     one or more request streams, offered side by side:

    loop        its arrival process, `bench/traffic/<loop>.py` (`poisson`:
                an open loop at `rate_per_s`; `closed`: `clients`
                clients, each sending its next request on the answer to
                its last, at most `rounds` each)
    table, column, tenant
                where its requests go
    mix         [{"op": <op>, "share": s, "keys": <chooser>, ...}]: each
                op is `bench/ops/<op>.py`, each key chooser
                `bench/traffic/<chooser>.py`, with the chooser's own
                parameters in the entry
    warm        entries drawn in turn to fill each warm-up batch (reads
                whose keys the window's writes do not decide)

A new mix, arrival process, key chooser or op is a new file; none of
these modules names another.

A stream's requests are drawn so that every seed offers the same work
at the same moments: the number of requests and each op's count are
fixed by the loop and the shares; the order of the ops and the arrival
process's draws (gaps, clients) come from one stream that does not
depend on the seed; the seed draws the keys (and, through the data
generator, the rows).  A tail latency then measures the system, not
where a seed happened to put its bursts and its writes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness import spec


@dataclasses.dataclass
class Request:
    """One client request.  `values` are plaintexts (a range's (lo, hi),
    an eq's or an insert's (v,)).  `due` is seconds after the window
    opens, where the arrival process fixes it; `of` is the rid of the
    write that a read-back reads."""
    rid: int
    op: str
    values: Tuple[int, ...]
    due: Optional[float] = None
    client: int = 0
    stream: int = 0
    table: str = ""
    of: Optional[int] = None


@dataclasses.dataclass
class Schedule:
    """What a run offers: the window's requests, the warm-up batches
    (each one stream's, of a size the loop can draft) and a read of
    every write's key for after the window."""
    streams: List[dict]
    requests: List[Request]
    warm: List[List[Request]]
    readback: List[Request]
    batch: int = 1


class KeySpace:
    """One table's keys as the choosers see them: its rows' values, the
    run's key stream `rng`, and the records written so far in insert
    order (`written`).  `cache` holds what a chooser computes once."""

    def __init__(self, data: dict, rng: np.random.Generator):
        self.data = data
        self.rng = rng
        self.values = np.asarray(data["values"])
        self.written = 0
        self.cache: Dict[object, object] = {}

    def value_of(self, keynum: int) -> int:
        """The key of record `keynum` in insert order (past the loaded
        rows: the data generator's `next_values`)."""
        n = len(self.values)
        if keynum < n:
            return int(self.values[keynum])
        return int(self.data["next_values"](keynum - n, 1)[0])


def _counts(mix: List[dict], n: int) -> List[int]:
    """Each entry's share of `n` requests, rounded so they sum to n."""
    raw = [m["share"] * n for m in mix]
    out = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(mix)), key=lambda i: out[i] - raw[i])
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def _draw(entry: dict, keys: KeySpace) -> Tuple[int, ...]:
    return spec.load_module("traffic", entry["keys"]).draw(entry, keys)


def make_schedule(mix_file: dict, data: Dict[str, dict], seed: int,
                  seconds: float) -> Schedule:
    """The schedule a run of `seconds` s offers under `mix_file`, drawn
    from `seed` over the tables `data` (datagen results by name)."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    shape = np.random.default_rng(0x7AFF1C)   # arrivals, op order: fixed
    spaces = {t: KeySpace(data[t], rng) for t in mix_file["tables"]}
    batch = int(mix_file["batch"])
    streams = mix_file["streams"]
    requests: List[Request] = []
    for si, st in enumerate(streams):
        loop = spec.load_module("traffic", st["loop"])
        mix = st["mix"]
        n = loop.count(st, seconds)
        labels = shape.permutation(
            np.repeat(np.arange(len(mix)), _counts(mix, n)))
        mine = []
        for i in labels:
            entry = mix[int(i)]
            mine.append(Request(len(requests) + len(mine), entry["op"],
                                _draw(entry, spaces[st["table"]]),
                                stream=si, table=st["table"]))
        loop.place(mine, st, seconds, shape)
        requests += mine
    rid = len(requests)
    warm = []
    for si, st in enumerate(streams):
        loop = spec.load_module("traffic", st["loop"])
        entries = st["warm"]
        for b in loop.warm_sizes(st, batch):
            group = []
            for j in range(b):  # exactly b, so the loop drafts a batch of b
                entry = entries[j % len(entries)]
                group.append(Request(rid, entry["op"],
                                     _draw(entry, spaces[st["table"]]),
                                     stream=si, table=st["table"]))
                rid += 1
            warm.append(group)
    readback = []
    for req in requests:
        back = getattr(op_module(req.op), "readback", None)
        if back is not None:
            op, values = back(req.values)
            readback.append(Request(rid, op, values, stream=req.stream,
                                    table=req.table, of=req.rid))
            rid += 1
    return Schedule(streams, requests, warm, readback, batch=batch)


def op_module(op: str):
    """The op `bench/ops/<op>.py`: how it is sent and what it answers."""
    return spec.load_module("ops", op)
