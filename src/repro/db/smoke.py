"""End-to-end run of the served path, checked against numpy.

The body of `chip_smoke.py`, as functions of the profile and the row
counts so the same phases run here on the CPU at a small profile (tier-1
`tests/test_chip_smoke.py`) and on a TPU at the paper's widths.

`run_smoke` (one device): an hg38 column as a `Table`, a prefix of it as
a second, indexed `Table`, both `QueryServer`s behind one `ServeLoop`;
range scans (the fused scan launch), indexed point lookups (index
probes), a top-k (the merge network), and an insert followed by reads
that must see it (delta run + union read).

`run_sharded_smoke` (several devices): the column as a `ShardedTable`
on a mesh of exactly `devices` devices with a `ShardedIndex`, behind
`ShardedQueryServer`s in a `ServeLoop`: indexed ranges and points
(fan-out probes) and fused sharded scans (`shard_map`), one round.

Every answer is compared exactly with a plain numpy evaluation of the
same predicate on the plaintext column: the same row-id set, in the same
order where the query orders.  Any difference, and any request that
does not come back OK, raises — no phase catches and carries on.  Each
phase logs its wall time split into compile seconds (lowering and
backend compile, from JAX's monitoring events) and the rest, the
programs it compiled, and the device's peak memory where the backend
reports it.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encrypt as E
from repro.core.keys import KeySet, keygen
from repro.core.params import make_params
from repro.db import plan as P
from repro.db.index import SortedIndex
from repro.db.query_serve import QueryServer, dataset_column
from repro.db.serve_loop import OK, ServeLoop
from repro.db.table import Table
from repro.kernels import ops as KO

# lowering to MLIR and the backend compile (tracing is left out: nested
# jits trace inside their caller's trace, so its events overlap)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Programs compiled and seconds spent compiling, process-wide, from
    JAX's monitoring events (a persistent-cache hit counts as a hit, not
    a compiled program)."""

    def __init__(self):
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
            if event == _COMPILE_EVENTS[-1]:
                self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        """(programs compiled, compile seconds, cache hits) so far."""
        return self.programs, self.compile_s, self.cache_hits


_METER: Optional[CompileMeter] = None


def compile_meter() -> CompileMeter:
    """The process's one meter (JAX listeners cannot be removed)."""
    global _METER
    if _METER is None:
        _METER = CompileMeter()
    return _METER


def peak_bytes(device=None) -> Optional[int]:
    """`peak_bytes_in_use` of `device` (default: the first local one;
    None on a backend that reports no memory statistics, such as the
    CPU)."""
    stats = (device or jax.local_devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str, record: Dict[str, dict], log: Callable[[str], None]):
    """Time one phase; record and log wall, compile and run seconds."""
    meter = compile_meter()
    p0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    p1, c1, h1 = meter.snapshot()
    rec = {"wall_s": wall, "compile_s": c1 - c0, "run_s": wall - (c1 - c0),
           "programs": p1 - p0, "cache_hits": h1 - h0,
           "peak_bytes_in_use": peak_bytes()}
    record[name] = rec
    log(f"phase {name}: wall {wall:.3f}s = compile {rec['compile_s']:.3f}s"
        f" + run {rec['run_s']:.3f}s; {rec['programs']} programs compiled,"
        f" {rec['cache_hits']} cache hits;"
        f" peak_bytes_in_use {rec['peak_bytes_in_use']}")


def _expect(name: str, got, want) -> None:
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(
            f"{name}: answer differs from the numpy reference "
            f"({got.size} rows, want {want.size}; first got "
            f"{got[:8].tolist()}, want {want[:8].tolist()})")


def _serve(loop: ServeLoop, tickets: Dict[int, tuple]) -> Dict[int, object]:
    """Drain the loop; every ticket must come back OK."""
    loop.run_until_idle()
    out = {}
    for t, (label, _) in tickets.items():
        r = loop.forget(t)
        if r is None or r.status != OK:
            raise RuntimeError(f"{label}: request came back "
                               f"{getattr(r, 'status', 'missing')} "
                               f"{getattr(r, 'error', '')}")
        out[t] = r.result
    return out


def _check_rows(results: Dict[int, object], tickets: Dict[int, tuple]):
    for t, (label, want) in tickets.items():
        _expect(label, results[t].row_ids, want)


def _topk_band(vals: np.ndarray, k: int, rng: np.random.Generator):
    """A value band [lo, hi] holding about 4k rows."""
    s = np.sort(vals)
    i = int(rng.integers(0, max(1, len(s) - 4 * k)))
    return int(s[i]), int(s[min(len(s) - 1, i + 4 * k)])


def _topk_reference(vals: np.ndarray, lo: int, hi: int, k: int):
    """The k largest values in [lo, hi], descending, with their ids
    (ties broken by id)."""
    ids = np.nonzero((vals >= lo) & (vals <= hi))[0]
    order = np.lexsort((ids, -vals[ids]))
    return ids[order][:k]


def _check_topk(label: str, vals: np.ndarray, lo: int, hi: int,
                got) -> None:
    """The top-k answer is exact when its values are the reference's
    value sequence and its ids are distinct rows of the band: rows that
    tie on value are interchangeable, in order and at the cut."""
    got = np.asarray(got, np.int64)
    want = _topk_reference(vals, lo, hi, len(got))
    _expect(label + " (values)", vals[got], vals[want])
    band = np.nonzero((vals >= lo) & (vals <= hi))[0]
    _expect(label + " (distinct band rows)",
            np.unique(got[np.isin(got, band)]), np.sort(got))


def _encryptor(ks: KeySet, rng: np.random.Generator):
    def enc(v: int):
        return E.encrypt(ks, jnp.asarray(int(v), jnp.int64),
                         jax.random.PRNGKey(int(rng.integers(1 << 30))))
    return enc


def run_smoke(profile: str = "paper-bfv", mode: str = "gadget", *,
              rows: Optional[int] = None, index_rows: int = 8192,
              batch: int = 4, seed: int = 0,
              log: Callable[[str], None] = print) -> Dict[str, dict]:
    """The one-device served path over the hg38 column (`rows` = None:
    all 34,423 rows).  The index covers the column's first `index_rows`
    rows in a second table.  Returns the phase records."""
    params = make_params(profile, mode=mode)
    record: Dict[str, dict] = {}
    with phase("keygen", record, log):
        ks = keygen(params, jax.random.PRNGKey(seed))
        jax.block_until_ready(ks.cek_rev)
    vals = dataset_column(params, "hg38", rows)
    pvals = vals[:index_rows]
    with phase("ingest", record, log):
        table = Table.from_arrays(ks, "hg38", {"value": vals},
                                  jax.random.PRNGKey(seed + 1))
        ptable = Table.from_arrays(ks, "hg38_prefix", {"value": pvals},
                                   jax.random.PRNGKey(seed + 2))
        jax.block_until_ready((table.columns["value"],
                               ptable.columns["value"]))
    log(f"tables: hg38 {table.n_rows} rows (padded {table.n_padded}), "
        f"hg38_prefix {ptable.n_rows} rows (padded {ptable.n_padded}); "
        f"{table.ciphertext_bytes() + ptable.ciphertext_bytes()} "
        f"ciphertext bytes on the device")
    with phase("index_build", record, log):
        idx = SortedIndex.build(ks, ptable, "value")
    log(f"index: hg38_prefix.value over the first {idx.n_rows} rows, "
        f"{idx.build_compares} build compares")
    budget = KO.device_lane_budget(params)
    log(f"lane budget {budget}; a {2 * batch}-atom scan tiles "
        f"{KO.lane_tile(table.scan_width, 2 * batch, None, default=budget)}"
        f" rows per launch")

    loop = ServeLoop(batch=batch)
    loop.register("hg38", QueryServer(ks, table, batch=batch))
    loop.register("hg38_prefix", QueryServer(ks, ptable, batch=batch,
                                             indexes={"value": idx}))
    rng = np.random.default_rng(seed)
    enc = _encryptor(ks, rng)

    for rnd in ("cold", "warm"):
        with phase(f"range_scans_{rnd}", record, log):
            tickets = {}
            for _ in range(batch):
                lo, hi = np.sort(rng.choice(vals, 2, replace=False))
                t = loop.submit("smoke", "hg38",
                                P.Range("value", enc(lo), enc(hi)))
                tickets[t] = (f"range [{lo}, {hi}]",
                              np.nonzero((vals >= lo) & (vals <= hi))[0])
            _check_rows(_serve(loop, tickets), tickets)
        with phase(f"point_lookups_{rnd}", record, log):
            tickets = {}
            probes = list(rng.choice(pvals, batch - 1))
            probes.append(int(pvals.max()) + 1)          # absent value
            for v in probes:
                t = loop.submit("smoke", "hg38_prefix",
                                P.Eq("value", enc(v)))
                tickets[t] = (f"point {v}", np.nonzero(pvals == v)[0])
            _check_rows(_serve(loop, tickets), tickets)

    with phase("topk", record, log):
        k = 8
        lo, hi = _topk_band(vals, k, rng)
        t = loop.submit("smoke", "hg38", P.Query(
            where=P.Range("value", enc(lo), enc(hi)),
            top_k=P.TopK("value", k)))
        res = _serve(loop, {t: ("topk", None)})[t]
        _expect("top-k size", [len(res.row_ids)],
                [min(k, int(((vals >= lo) & (vals <= hi)).sum()))])
        _check_topk(f"top-{k} of [{lo}, {hi}]", vals, lo, hi, res.row_ids)

    with phase("insert_then_read", record, log):
        v_new = int(pvals.max()) + 7
        ins = loop.submit_insert("smoke", "hg38_prefix",
                                 {"value": np.asarray([v_new], np.int64)},
                                 jax.random.PRNGKey(seed + 3))
        lo = int(np.percentile(pvals, 99))
        tickets = {
            loop.submit("smoke", "hg38_prefix", P.Eq("value", enc(v_new))):
                (f"point {v_new} after insert", [len(pvals)]),
            loop.submit("smoke", "hg38_prefix",
                        P.Range("value", enc(lo), enc(v_new))):
                (f"range [{lo}, {v_new}] after insert",
                 np.append(np.nonzero(pvals >= lo)[0], len(pvals))),
        }
        results = _serve(loop, {ins: ("insert", None), **tickets})
        _expect("insert ids", results[ins].row_ids, [len(pvals)])
        _check_rows(results, tickets)
    log(f"peak_bytes_in_use {peak_bytes()}")
    return record


def run_sharded_smoke(profile: str = "paper-bfv", mode: str = "gadget", *,
                      rows: Optional[int] = None, shards: int = 4,
                      devices: int = 4, batch: int = 4, seed: int = 0,
                      log: Callable[[str], None] = print
                      ) -> Dict[str, dict]:
    """The sharded served path over the hg38 column: `shards` shards on
    a mesh of exactly `devices` distinct devices (raises if the host
    has fewer), indexed and scanned.  Returns the phase records."""
    from repro.db.shard import ShardedIndex, ShardedQueryServer, ShardSpec
    from repro.db.shard.table import ShardedTable

    params = make_params(profile, mode=mode)
    record: Dict[str, dict] = {}
    spec = ShardSpec.create(shards)
    mesh_devices = sorted({d.id for d in spec.mesh.devices.flat})
    if len(mesh_devices) != devices:
        raise AssertionError(f"mesh holds devices {mesh_devices}, "
                             f"want {devices} distinct")
    log(f"mesh: {shards} shards over devices {mesh_devices}")
    with phase("keygen", record, log):
        ks = keygen(params, jax.random.PRNGKey(seed))
        jax.block_until_ready(ks.cek_rev)
    vals = dataset_column(params, "hg38", rows)
    with phase("ingest", record, log):
        st = ShardedTable.from_arrays(ks, "hg38", {"value": vals},
                                      jax.random.PRNGKey(seed + 1),
                                      spec=spec)
        jax.block_until_ready(st.columns["value"])
    placed = sorted({d.id for d in st.columns["value"].c0.devices()})
    if placed != mesh_devices:
        raise AssertionError(f"column lives on devices {placed}, "
                             f"want {mesh_devices}")
    log(f"table: hg38 {st.n_rows} rows in {shards} shards of "
        f"{st.n_padded_per_shard} padded rows")
    with phase("index_build", record, log):
        sidx = ShardedIndex.build(ks, st, "value")
    log(f"index: hg38.value over all {int(sidx.counts.sum())} rows, "
        f"{sidx.build_compares} build compares")

    loop = ServeLoop(batch=batch)
    loop.register("hg38", ShardedQueryServer(ks, st, batch=batch,
                                             indexes={"value": sidx}))
    loop.register("hg38_scan", ShardedQueryServer(ks, st, batch=batch))
    rng = np.random.default_rng(seed)
    enc = _encryptor(ks, rng)
    with phase("indexed", record, log):
        tickets = {}
        for v in rng.choice(vals, batch // 2):
            t = loop.submit("smoke", "hg38", P.Eq("value", enc(v)))
            tickets[t] = (f"point {v}", np.nonzero(vals == v)[0])
        for _ in range(batch - batch // 2):
            lo, hi = np.sort(rng.choice(vals, 2, replace=False))
            t = loop.submit("smoke", "hg38",
                            P.Range("value", enc(lo), enc(hi)))
            tickets[t] = (f"indexed range [{lo}, {hi}]",
                          np.nonzero((vals >= lo) & (vals <= hi))[0])
        _check_rows(_serve(loop, tickets), tickets)
    with phase("range_scans", record, log):
        tickets = {}
        for _ in range(batch):
            lo, hi = np.sort(rng.choice(vals, 2, replace=False))
            t = loop.submit("smoke", "hg38_scan",
                            P.Range("value", enc(lo), enc(hi)))
            tickets[t] = (f"sharded scan [{lo}, {hi}]",
                          np.nonzero((vals >= lo) & (vals <= hi))[0])
        _check_rows(_serve(loop, tickets), tickets)
    log("peak_bytes_in_use per mesh device " + ", ".join(
        f"{d.id}: {peak_bytes(d)}" for d in spec.mesh.devices.flat))
    return record


def summary(record: Dict[str, dict]) -> Dict[str, float]:
    """Totals over a run's phases: programs compiled, compile and run
    seconds."""
    return {"programs": sum(r["programs"] for r in record.values()),
            "compile_s": sum(r["compile_s"] for r in record.values()),
            "run_s": sum(r["run_s"] for r in record.values())}

