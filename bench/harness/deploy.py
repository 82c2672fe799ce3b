"""Build a configuration's deployment on the device and warm it up.

The configuration names its builder (`deploy`): `bench/deploys/<name>.py`,
whose `build` makes the tables, indexes and serving loop and returns the
target the window drives.  What every builder shares is here: the
tables' plaintext data from the seed, the run's keys, the client-side
trapdoor pool, and serving a warm-up batch.

Phases (each timed by `meter.phase`): a builder's own (`keygen`,
`ingest.<table>`, `index_build.<table>`, ...), then `trapdoor_pool`
(every read's trapdoors, encrypted client-side in batched jitted calls
from the seed and handed to the server as host bytes, one fresh
trapdoor per request) and `warmup` (the mix's own batch shapes, through
the served path).
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

from harness import spec
from harness.meter import phase
from harness.traffic import op_module

POOL_CHUNK = 1024       # trapdoors per encrypt launch (one program shape)


def raw_key(seed: int, *tag: int) -> np.ndarray:
    """A raw uint32[2] PRNG key drawn from the run's seed and `tag`."""
    return np.random.default_rng([seed, *tag]).integers(
        0, 1 << 32, size=2, dtype=np.uint32)


def table_data(cfg: dict, names: List[str], seed: int) -> Dict[str, dict]:
    """Plaintext data of the tables `names` (and the tables they are
    prefixes of), from each table's data generator."""
    entries = {t["name"]: t for t in cfg["tables"]}
    out: Dict[str, dict] = {}

    def make(name: str) -> dict:
        if name not in out:
            t = entries[name]
            if "prefix_of" in t:
                parent = make(t["prefix_of"])
                out[name] = {**parent,
                             "values": parent["values"][:int(t["rows"])]}
            else:
                gen = spec.load_module("datagen", t["datagen"])
                out[name] = gen.make(t, seed)
        return out[name]
    for n in names:
        make(n)
    return out


def build(cfg: dict, mix: dict, data: Dict[str, dict], schedule, seed: int,
          record: Dict[str, dict], log: Callable[[str], None],
          peak: Callable[[], object]):
    """The served deployment with the cell's shapes warmed up, by the
    configuration's builder."""
    return spec.load_module("deploys", cfg["deploy"]).build(
        cfg, mix, data, schedule, seed, record, log, peak)


def _encrypted_requests(schedule) -> list:
    reqs = [r for r in schedule.requests
            if op_module(r.op).CLIENT_ENCRYPTS]
    for group in schedule.warm:
        reqs += group
    return reqs + schedule.readback


def trapdoor_pool(ks, params, schedule, seed: int, record: Dict[str, dict],
                  log: Callable[[str], None], peak: Callable[[], object]):
    """Every client-encrypted value of the schedule (window, warm-up and
    read-backs), encrypted on the device in POOL_CHUNK launches of one
    jitted program, brought back as host bytes: {rid: [(c0, c1), ...]}.
    Also each write's raw key: {rid: uint32[2]}."""
    import jax
    import jax.numpy as jnp

    from repro.core import encrypt as E
    with phase("trapdoor_pool", record, log, peak):
        reads = _encrypted_requests(schedule)
        vals = np.asarray([v for r in reads for v in r.values], np.int64)
        enc = jax.jit(lambda m, key: E.encrypt(ks, m, key))
        n = len(vals)
        padded = np.zeros(-(-n // POOL_CHUNK) * POOL_CHUNK, np.int64)
        padded[:n] = vals
        c0 = np.empty((len(padded), params.num_towers, params.n), np.int64)
        c1 = np.empty_like(c0)
        for lo in range(0, len(padded), POOL_CHUNK):
            ct = enc(jnp.asarray(padded[lo:lo + POOL_CHUNK]),
                     raw_key(seed, 2, lo))
            c0[lo:lo + POOL_CHUNK] = np.asarray(ct.c0)
            c1[lo:lo + POOL_CHUNK] = np.asarray(ct.c1)
        trapdoors, k = {}, 0
        for r in reads:
            trapdoors[r.rid] = [(c0[k + j], c1[k + j])
                                for j in range(len(r.values))]
            k += len(r.values)
        writes = [r for r in schedule.requests if op_module(r.op).WRITES]
        write_keys = {r.rid: raw_key(seed, 3, r.rid) for r in writes}
        log(f"trapdoor pool: {n} trapdoors for {len(reads)} reads "
            f"({c0.nbytes + c1.nbytes} host bytes); {len(writes)} writes")
    return trapdoors, write_keys


def serve(target, group, log: Callable[[str], None]) -> None:
    """Serve `group` through `target` to the end; log what fails (the
    window's comparison is what judges the program)."""
    tickets = [target.submit(r) for r in group]
    target.loop.run_until_idle()
    for t in tickets:
        status, _, _, err = target.take(t)
        if status != "OK":
            log(f"warm-up request came back {status}: {err}")
