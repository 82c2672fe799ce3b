"""Part 6: watching the engine work — `repro.obs` end to end.

One encrypted range query (linear scan, then through the HADES sorted
index) runs under a trace; the demo prints the nested span tree with
host timings, the counter table the run produced, the jit-cache
observer's launch signatures, and writes a Chrome-trace JSON you can
drop into ui.perfetto.dev.  The same run is also under `jax.profiler`:
each span sits in its `.xplane.pb` beside the device's ops, carrying
its `sid` and `parent`, and the demo lists them from there.

    PYTHONPATH=src python examples/part6_observability.py
    PYTHONPATH=src python examples/part6_observability.py \
        --rows 2048 --trace-out /tmp/trace.json

The parts 1-5 tour (primitives, engine, floats, shards, joins) lives
in examples/encrypted_range_query.py; this file is the observability
chapter: where the launches go, what each one cost, and how to tell a
healthy batch from a broken one (span taxonomy and counter glossary:
docs/architecture.md §8).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import db, obs
from repro.core import encrypt as E
from repro.core.keys import keygen
from repro.core.params import make_params
from repro.data import load_dataset


def main(argv=None):
    """Trace one encrypted range query; print spans + counters."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024,
                    help="hg38 rows to load (0 = all 34,423)")
    ap.add_argument("--trace-out", default="obs_trace.json",
                    help="Chrome-trace JSON output path ('' = skip)")
    ap.add_argument("--profile-dir", default="obs_profile",
                    help="jax.profiler trace directory (TensorBoard / "
                         "Perfetto via xprof)")
    args = ap.parse_args(argv)

    params = make_params("test-bfv", mode="gadget")
    ks = keygen(params, jax.random.PRNGKey(0))
    vals = load_dataset("hg38", scheme="bfv", t=params.t).astype(np.int64)
    if args.rows:
        vals = vals[:args.rows]

    print(f"--- setup: {len(vals)} hg38 rows, encrypt + index ---")
    t0 = time.time()
    table = db.Table.from_arrays(ks, "hg38", {"pos": vals},
                                 jax.random.PRNGKey(1))
    idx = db.SortedIndex.build(ks, table, "pos")
    print(f"table {table.n_rows} rows (padded {table.n_padded}), index "
          f"built with {idx.build_compares} compares ({time.time()-t0:.1f}s)")

    def enc(v, s):
        return E.encrypt(ks, jnp.asarray(int(v)), jax.random.PRNGKey(s))

    lo, hi = int(np.percentile(vals, 40)), int(np.percentile(vals, 60))
    q = db.Range("pos", enc(lo, 2), enc(hi, 3))
    db.execute(ks, table, q)                          # warm jit (untraced)
    db.execute(ks, table, q, indexes={"pos": idx})

    # ---- the traced run: linear scan, then the indexed path -------------
    print(f"\n--- traced: Range[{lo}, {hi}] linear + indexed ---")
    with jax.profiler.trace(args.profile_dir), obs.tracing() as tr:
        with obs.span("demo.linear"):
            lin = db.execute(ks, table, q)
        with obs.span("demo.indexed"):
            ind = db.execute(ks, table, q, indexes={"pos": idx})
    assert np.array_equal(lin.mask, ind.mask)

    print("\nspan tree (host ms; a span that reads a result back waits "
          "for the device):")
    for line in tr.tree_lines():
        print(f"  {line}")

    # the same spans in the profiler's trace, on the device's clock
    prof = obs.profiler_spans(args.profile_dir)
    t0 = prof[0]["start_ns"] if prof else 0
    print(f"\nprofiler trace ({args.profile_dir}): {len(prof)} spans "
          "(first 8; sid, parent, start and length in ms):")
    for e in prof[:8]:
        print(f"  {e['name']:<22} sid={e['sid']:<3} parent={e['parent']:<3} "
              f"+{(e['start_ns'] - t0) / 1e6:8.3f}  {e['dur_ns'] / 1e6:8.3f}")

    print("\ncounter table:")
    snap = obs.REGISTRY.snapshot()
    width = max(len(k) for k in snap)
    for name, v in snap.items():
        if isinstance(v, dict):                       # histogram summary
            v = (f"count={v['count']:.0f} p50={v['p50']:.3g} "
                 f"p99={v['p99']:.3g}")
        print(f"  {name:<{width}}  {v}")

    print("\njit-cache observer (signatures per launch site):")
    for site, sigs in obs.jit_signatures().items():
        flag = "" if len(sigs) == 1 else "  <-- RETRACES"
        print(f"  {site}: {len(sigs)} signature(s){flag}")

    f = obs.bench_fields()
    print(f"\nlaunch accounting: {f['eval_launches']} launches, "
          f"{f['compare_lanes']} compare lanes, "
          f"{f['jit_retraces']} retraces")
    print(f"  linear scan:  {lin.stats.scan_compares} compares in "
          f"{lin.stats.eval_calls} fused launch")
    print(f"  indexed path: {ind.stats.index_compares} probe compares "
          f"(binary search, ~2*log2 n)")

    if args.trace_out:
        tr.write_chrome_trace(args.trace_out)
        errs = obs.validate_chrome_trace(tr.chrome_trace())
        print(f"\nwrote {args.trace_out} "
              f"(valid Chrome trace: {not errs}) — open at ui.perfetto.dev")


if __name__ == "__main__":
    main()
