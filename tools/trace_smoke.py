#!/usr/bin/env python
"""CI trace smoke: one traced QueryServer batch must export a valid
Chrome trace.

Runs a small encrypted table through a batched `QueryServer` drain
under `obs.tracing()`, then fails loudly unless:

  * the export is structurally valid Chrome-trace JSON — every event
    carries `ph` / `ts` / `pid` (checked event by event here, on top of
    `obs.validate_chrome_trace`);
  * the spans the batch MUST produce are present: the batch span, the
    fused raw-eval launch, and the index binary search;
  * the server runs with a deliberately tiny `lane_budget`, so the
    fused scan splits into lane tiles — every `executor.eval_tile`
    span must nest under an `executor.fused_eval` parent (the tiling
    must refine the launch accounting, never restructure the tree);
  * per-query compare lanes reconcile exactly with the batch totals;
  * the same spans sit in the `jax.profiler` trace of the batch (the
    `.xplane.pb`, on one clock with the device's ops), each with the
    `Tracer`'s `sid` and `parent`.

The trace lands at --out (default trace_smoke.json) and CI uploads it
as a workflow artifact, so every green run leaves an openable
ui.perfetto.dev trace behind.

Usage:  PYTHONPATH=src python tools/trace_smoke.py [--out trace.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile

import jax
import numpy as np

from repro import db, obs
from repro.core import encrypt as E
from repro.core.keys import keygen
from repro.core.params import make_params


def main(argv=None) -> int:
    """Run the traced batch; validate; write the trace artifact."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace_smoke.json")
    args = ap.parse_args(argv)

    ks = keygen(make_params("test-bfv", mode="gadget"),
                jax.random.PRNGKey(0))
    vals = np.array([3, 14, 15, 9, 26, 5, 35, 8, 97, 93, 23, 84], np.int64)
    aux = np.array([1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3], np.int64)
    table = db.Table.from_arrays(ks, "smoke", {"v": vals, "a": aux},
                                 jax.random.PRNGKey(1))
    idx = db.SortedIndex.build(ks, table, "v")   # "a" stays unindexed

    def enc(v, s):
        return E.encrypt(ks, np.int64(int(v)), jax.random.PRNGKey(s))

    # one batch mixing indexed lanes ("v") and a fused-scan atom: both
    # launch kinds must show up in the trace.  lane_budget=8 forces the
    # 16-wide fused scan into 2 tiles so the tile spans are exercised.
    server = db.QueryServer(ks, table, indexes={"v": idx}, batch=3,
                            lane_budget=8)
    qids = [server.submit(db.Range("v", enc(5, 2), enc(30, 3))),
            server.submit(db.Eq("a", enc(2, 4))),    # unindexed -> scan
            server.submit(db.Query(where=db.Range("v", enc(3, 5),
                                                  enc(95, 6)),
                                   top_k=db.TopK("v", 3)))]
    with tempfile.TemporaryDirectory() as prof:
        with jax.profiler.trace(prof), obs.tracing() as tr:
            results = server.run()
            spans = list(tr.spans)
            tr.write_chrome_trace(args.out)
        in_profile = obs.profiler_spans(prof)

    errors = []

    # the profiler's trace holds every span, with the Tracer's tree
    want = {(s.sid, s.parent_sid, s.name) for s in spans}
    got = {(e["sid"], e["parent"], e["name"]) for e in in_profile}
    if got != want:
        errors.append(f"profiler trace spans differ from the Tracer's: "
                      f"{len(want - got)} missing, {len(got - want)} extra")

    # tile spans must NEST under the fused launch: the lane tiling is a
    # refinement of executor.fused_eval, not a sibling of it
    by_sid = {s.sid: s for s in spans}
    tiles = [s for s in spans if s.name == "executor.eval_tile"]
    if len(tiles) < 2:
        errors.append(f"lane_budget=8 on a 16-wide scan must produce "
                      f">=2 executor.eval_tile spans, got {len(tiles)}")
    for s in tiles:
        parent = by_sid.get(s.parent_sid)
        if parent is None or parent.name != "executor.fused_eval":
            errors.append(
                f"executor.eval_tile span (sid={s.sid}) not nested under "
                f"executor.fused_eval (parent="
                f"{parent.name if parent else None})")

    doc = json.load(open(args.out))
    errors += obs.validate_chrome_trace(doc)
    events = doc.get("traceEvents", [])
    for i, ev in enumerate(events):
        for field in ("ph", "ts", "pid"):
            if field not in ev:
                errors.append(f"event {i} missing {field!r}: {ev}")

    names = {ev.get("name") for ev in events}
    for must in ("server.batch", "index.search", "executor.fused_eval"):
        if must not in names:
            errors.append(f"required span {must!r} absent from trace")

    b = server.batch_log[-1]
    per_q = sum(results[q].stats.index_compares for q in qids)
    if per_q != b.index_compares:
        errors.append(f"per-query index compares {per_q} != "
                      f"batch total {b.index_compares}")
    per_s = sum(results[q].stats.scan_compares for q in qids)
    if per_s != b.scan_compares:
        errors.append(f"per-query scan compares {per_s} != "
                      f"batch total {b.scan_compares}")

    for e in errors:
        print(f"FAIL {e}")
    if errors:
        return 1
    print(f"trace smoke passed: {len(events)} events -> {args.out}, "
          f"{len(in_profile)} spans in the profiler trace "
          f"(batch: {b.queries} queries, {b.eval_calls} fused launch, "
          f"{b.index_compares} probe + {b.scan_compares} scan lanes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
