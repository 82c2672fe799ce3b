"""What a window drives: the system under test, or the control.

Both answer `submit(request) -> ticket`, `pending()`, `pump()` and
`take(ticket) -> None | (status, row_ids, done_t, error)`.

`ProgramTarget` is the served path: each request goes to one
`ServeLoop` by its op's `submit` (`bench/ops/<op>.py`), and `pump` runs
the loop's scheduling round in front of the `QueryServer`s.  Trapdoors
come from the client-side pool made in set-up; the server receives them
as host bytes, as it would from a client.

`ControlTarget` puts the plain reference in the program's place with
one guarantee of the configuration broken (`reference.answer`); the
comparison must refuse it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness import reference
from harness.traffic import op_module


@dataclasses.dataclass
class Via:
    """What an op's `submit` sends a request with: the loop, where the
    request goes, its trapdoors and its write key."""
    loop: object
    tenant: str
    table: str
    column: str
    trapdoors: list
    key: Optional[np.ndarray]


class ProgramTarget:
    """Requests through one `ServeLoop` to the tables of their streams."""

    def __init__(self, loop, streams: List[dict],
                 trapdoors: Dict[int, List[Tuple[np.ndarray, np.ndarray]]],
                 write_keys: Dict[int, np.ndarray]):
        self.loop = loop
        self.streams = streams
        self.trapdoors = trapdoors          # rid -> [(c0, c1), ...]
        self.write_keys = write_keys        # rid -> raw uint32[2] key

    def submit(self, req) -> int:
        from repro.core.encrypt import Ciphertext
        st = self.streams[req.stream]
        cts = [Ciphertext(c0, c1)
               for c0, c1 in self.trapdoors.get(req.rid, ())]
        via = Via(self.loop, st["tenant"], st["table"], st["column"], cts,
                  self.write_keys.get(req.rid))
        return op_module(req.op).submit(via, req)

    def pending(self) -> bool:
        return self.loop.queue_depth() > 0

    def pump(self) -> None:
        self.loop.pump()

    def take(self, ticket: int) -> Optional[tuple]:
        resp = self.loop.response(ticket)
        if not resp.done:
            return None
        self.loop.forget(ticket)
        rows = None if resp.result is None else np.asarray(
            resp.result.row_ids, np.int64)
        return resp.status, rows, resp.done_t, resp.error

    def close(self) -> None:
        self.loop = None
        self.trapdoors = {}


class ControlTarget:
    """The reference in the program's place, with `broken` guarantee:
    every pump answers all pending requests in submission order."""

    def __init__(self, bases: Dict[str, np.ndarray], broken: str,
                 clock=time.perf_counter):
        self.bases = {t: np.asarray(b, np.int64) for t, b in bases.items()}
        self.broken = broken
        self.clock = clock
        self.written: Dict[str, List[int]] = {t: [] for t in bases}
        self._queue: List[Tuple[int, object]] = []
        self._done: Dict[int, tuple] = {}
        self._next = 0

    def submit(self, req) -> int:
        t = self._next
        self._next += 1
        self._queue.append((t, req))
        return t

    def pending(self) -> bool:
        return bool(self._queue)

    def pump(self) -> None:
        for t, req in self._queue:
            rows = reference.answer(req.op, req.values, self.bases[req.table],
                                    self.written[req.table],
                                    broken=self.broken)
            mod = op_module(req.op)
            if mod.WRITES:
                mod.apply(req.values, self.written[req.table])
            self._done[t] = (reference.OK, rows, self.clock(), "")
        self._queue = []

    def take(self, ticket: int) -> Optional[tuple]:
        return self._done.pop(ticket, None)

    def close(self) -> None:
        pass
