"""Builder `one_chip`: every table of the mix on one chip, each with the
`SortedIndex`es its configuration entry asks for, behind one `ServeLoop`
of `QueryServer`s, with the mix's `batch` and, where the mix states it,
its `tenant_queue_cap` (else the loop's default admission policy).

Phases: `keygen`; `ingest.<table>` and `index_build.<table>` for every
table the mix loads, in the configuration's order; the shared
`trapdoor_pool`; `warmup` (the mix's warm-up batches through the served
path; for a stream that writes, its writes one by one on a scratch
table, each followed by every warm-up batch, so that every delta-run
size the window reaches has its programs built; the window's tables are
never written before the window).

Keys come from the configuration's fixed `key_seed`: the program's
compiled programs hold the keys as constants, so a fresh key per run
would recompile everything.  Data, encryption randomness and traffic
come from the run's seed.
"""
import time

from harness.deploy import raw_key, serve, trapdoor_pool
from harness.meter import phase
from harness.targets import ProgramTarget
from harness.traffic import op_module

SCRATCH_ROWS = 4        # loaded rows of a table that warms the write path


def build(cfg, mix, data, schedule, seed, record, log, peak):
    import jax

    from repro.core.keys import keygen
    from repro.core.params import make_params
    from repro.db.serve_loop import AdmissionPolicy, ServeLoop

    params = make_params(cfg["profile"], mode=cfg["cek_mode"])
    with phase("keygen", record, log, peak):
        ks = keygen(params, jax.random.PRNGKey(int(cfg["key_seed"])))
        jax.block_until_ready(ks.cek_rev)

    batch = int(mix["batch"])
    policy = AdmissionPolicy()
    if "tenant_queue_cap" in mix:
        policy = AdmissionPolicy(tenant_queue_cap=int(mix["tenant_queue_cap"]))
    loop = ServeLoop(batch=batch, policy=policy, clock=time.perf_counter)
    for i, t in enumerate(cfg["tables"]):
        if t["name"] not in mix["tables"]:
            continue
        _serve_table(ks, loop, t, data[t["name"]]["values"],
                     raw_key(seed, 1, i), batch, record, log, peak)

    trapdoors, write_keys = trapdoor_pool(ks, params, schedule, seed,
                                          record, log, peak)
    target = ProgramTarget(loop, schedule.streams, trapdoors, write_keys)
    with phase("warmup", record, log, peak):
        for group in schedule.warm:
            serve(target, group, log)
        for si, st in enumerate(schedule.streams):
            if any(op_module(r.op).WRITES for r in schedule.requests
                   if r.stream == si):
                _warm_writes(ks, cfg, data, schedule, si, trapdoors, seed,
                             log)
    shapes = sorted({s[1:] for s in loop.batch_shapes})
    log(f"warm-up drafted batches (class, size): {shapes}")
    return target


def _serve_table(ks, loop, entry, values, key, batch, record, log, peak,
                 name=None):
    """Ingest one table (and its index) and register its server."""
    import jax

    from repro.db.index import SortedIndex
    from repro.db.query_serve import QueryServer
    from repro.db.table import Table

    name, col = name or entry["name"], entry["column"]
    with phase(f"ingest.{name}", record, log, peak):
        table = Table.from_arrays(ks, name, {col: values}, key)
        jax.block_until_ready(table.columns[col])
    indexes = {}
    if entry.get("index"):
        with phase(f"index_build.{name}", record, log, peak):
            indexes[col] = SortedIndex.build(ks, table, col)
            jax.block_until_ready(indexes[col].sorted_ct)
    log(f"table {name}: {table.n_rows} rows (padded {table.n_padded}), "
        f"{table.ciphertext_bytes()} ciphertext bytes"
        + (f", indexed on {col}" if indexes else ""))
    loop.register(name, QueryServer(ks, table, batch=batch,
                                    indexes=indexes))


def _warm_writes(ks, cfg, data, schedule, si, trapdoors, seed, log):
    """Compile stream `si`'s write path before the window on a scratch
    copy of its table (SCRATCH_ROWS loaded rows)."""
    from repro.db.serve_loop import ServeLoop

    st = schedule.streams[si]
    entry = next(t for t in cfg["tables"] if t["name"] == st["table"])
    loop = ServeLoop(batch=schedule.batch, clock=time.perf_counter)
    values = data[st["table"]]["values"][:SCRATCH_ROWS]
    _serve_table(ks, loop, entry, values, raw_key(seed, 4), schedule.batch,
                 {}, lambda line: None, lambda: None, name="_warm")
    keys = {r.rid: raw_key(seed, 5, r.rid) for r in schedule.requests
            if op_module(r.op).WRITES}
    scratch = [dict(s, table="_warm") for s in schedule.streams]
    target = ProgramTarget(loop, scratch, trapdoors, keys)
    for req in schedule.requests:
        if req.stream != si or not op_module(req.op).WRITES:
            continue
        serve(target, [req], log)
        for group in schedule.warm:
            if group[0].stream == si:
                serve(target, group, log)
