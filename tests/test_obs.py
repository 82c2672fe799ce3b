"""repro.obs: spans, metrics, launch accounting, and the counter
reconciliation contract between per-query ExecStats and BatchStats.

The reconciliation invariants (asserted here for scan, indexed, join,
and mutation batches, on both servers):

  * compare lanes ARE summable — sum of per-query scan_compares /
    index_compares over a drained batch equals the batch totals exactly
    (every lane belongs to exactly one query);
  * eval_calls are NOT summable — each query's share of the one fused
    launch is 1, the batch counts the launch once.

Span tests run with the tracer freshly enabled via `obs.tracing()`;
everything restores the prior disabled state on exit, so the rest of
the suite keeps the zero-overhead path.
"""
import json

import jax
import numpy as np
import pytest

from repro import db, obs
from repro.core import encrypt as E


def _enc(ks, v, seed):
    return E.encrypt(ks, np.int64(int(v)), jax.random.PRNGKey(seed))


def _table(ks, vals, name="t"):
    return db.Table.from_arrays(ks, name, {"v": np.asarray(vals, np.int64)},
                                jax.random.PRNGKey(2))


VALS = np.array([3, 14, 15, 9, 26, 5, 35, 8, 97, 93, 23, 84], np.int64)


# ---------------------------------------------------------------------------
# span / tracer primitives
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop_singleton():
    assert not obs.is_enabled()
    # spans an earlier traced test left behind (tracing clears on entry)
    # are not this test's concern: a disabled span must add none
    before = len(obs.TRACER.spans)
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2                      # no allocation on the hot path
    with s1 as sp:
        sp.set(y=2)                      # all no-ops
    assert len(obs.TRACER.spans) == before


def test_disabled_counters_do_not_record():
    assert not obs.is_enabled()
    before = dict(obs.REGISTRY.snapshot())
    obs.count("eval.launches", 5)
    obs.observe("pad.waste", 2.0)
    obs.jit_launch("nowhere", np.zeros((2, 2)))
    assert obs.REGISTRY.snapshot() == before


def test_span_nesting_parent_ids_and_depth():
    with obs.tracing():
        with obs.span("root", k="v"):
            with obs.span("child"):
                with obs.span("grandchild"):
                    pass
            with obs.span("child2"):
                pass
    by_name = {s.name: s for s in obs.TRACER.spans}
    root = by_name["root"]
    assert root.parent_sid == -1 and root.depth == 0
    assert by_name["child"].parent_sid == root.sid
    assert by_name["child2"].parent_sid == root.sid
    assert by_name["grandchild"].parent_sid == by_name["child"].sid
    assert by_name["grandchild"].depth == 2
    for s in obs.TRACER.spans:
        assert s.t1 >= s.t0


def test_spans_reach_the_profiler_trace(tmp_path):
    """Under `jax.profiler`, each span is a host event of its own name in
    the `.xplane.pb`, carrying the Tracer's `sid` and `parent`; args
    (set late too) stay in the Tracer, joined by sid."""
    f = jax.jit(lambda x: x * 2)
    x = jax.numpy.ones(8)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with obs.tracing() as tr:
            with obs.span("root", k="v") as sp:
                with obs.span("child"):
                    with obs.span("grandchild"):
                        f(x).block_until_ready()
                with obs.span("child2"):
                    pass
                sp.set(late=1)
    events = obs.profiler_spans(tmp_path)
    assert [e["name"] for e in events] == ["root", "child", "grandchild",
                                           "child2"]
    by_sid = {s.sid: s for s in tr.spans}
    for e in events:
        s = by_sid[e["sid"]]
        assert s.name == e["name"] and s.parent_sid == e["parent"]
        assert e["dur_ns"] > 0
    root = next(e for e in events if e["name"] == "root")
    assert root["parent"] == -1
    assert by_sid[root["sid"]].args == {"k": "v", "late": 1}
    for e in events:                     # children lie inside their parent
        if e["parent"] >= 0:
            p = next(q for q in events if q["sid"] == e["parent"])
            assert p["start_ns"] <= e["start_ns"]
            assert (e["start_ns"] + e["dur_ns"]
                    <= p["start_ns"] + p["dur_ns"])


def test_disabled_spans_leave_no_profiler_event(tmp_path):
    assert not obs.is_enabled()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("quiet", x=1) as sp:
            assert sp is obs.span("other")   # the shared null span
            jax.numpy.ones(4).block_until_ready()
    assert obs.profiler_spans(tmp_path) == []


def test_tracing_context_restores_disabled_state():
    assert not obs.is_enabled()
    with obs.tracing():
        assert obs.is_enabled()
    assert not obs.is_enabled()


def test_chrome_trace_shape_and_validation():
    with obs.tracing():
        with obs.span("outer", rows=4):
            with obs.span("inner"):
                pass
    doc = obs.chrome_trace()
    assert obs.validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert "pid" in ev and "tid" in ev and "dur" in ev
    # validation catches a broken event
    bad = {"traceEvents": [{"name": "x"}]}
    assert obs.validate_chrome_trace(bad) != []
    assert obs.validate_chrome_trace(json.dumps(doc)) == []


def test_write_chrome_trace_roundtrip(tmp_path):
    with obs.tracing():
        with obs.span("only"):
            pass
        path = tmp_path / "trace.json"
        obs.write_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert obs.validate_chrome_trace(loaded) == []
    assert loaded["traceEvents"][0]["name"] == "only"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_and_labels():
    reg = obs.Registry()
    reg.counter("q").inc()
    reg.counter("q", tenant="a").inc(3)
    reg.counter("q", tenant="b").inc(4)
    assert reg.value("q") == 1
    assert reg.value("q", tenant="a") == 3
    snap = reg.snapshot()
    assert snap["q{tenant=a}"] == 3 and snap["q{tenant=b}"] == 4


def test_histogram_percentiles_nearest_rank():
    reg = obs.Registry()
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["sum"] == 5050.0
    assert s["p50"] == 50.0 and s["p99"] == 99.0
    assert h.percentile(100) == 100.0


def test_registry_reset():
    reg = obs.Registry()
    reg.counter("x").inc(7)
    reg.reset()
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# jit-cache observer
# ---------------------------------------------------------------------------

def test_jitwatch_counts_signatures_and_retraces():
    with obs.tracing():
        a = np.zeros((4, 8), np.int64)
        obs.jit_launch("site.x", a)
        obs.jit_launch("site.x", a)              # same signature: no retrace
        assert obs.REGISTRY.value("jit.retraces") == 0
        obs.jit_launch("site.x", np.zeros((4, 16), np.int64))  # new shape
        assert obs.REGISTRY.value("jit.retraces") == 1
        assert obs.REGISTRY.value("jit.retraces", site="site.x") == 1
        assert obs.REGISTRY.value("launches", site="site.x") == 3
        sigs = obs.jit_signatures()
        assert len(sigs["site.x"]) == 2


def test_bench_fields_keys():
    with obs.tracing():
        obs.count("eval.launches")
        obs.count("eval.lanes", 64)
        f = obs.bench_fields()
    assert f == {"eval_launches": 1, "compare_lanes": 64, "jit_retraces": 0}


# ---------------------------------------------------------------------------
# traced engine paths: every launch appears as a span
# ---------------------------------------------------------------------------

def test_traced_scan_query_span_tree(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    q = db.Eq("v", _enc(ks, 15, 3))
    db.execute(ks, table, q)                     # warm outside the trace
    with obs.tracing():
        res = db.execute(ks, table, q)
    names = [s.name for s in obs.TRACER.spans]
    assert "executor.execute" in names
    assert names.count("executor.fused_eval") == res.stats.eval_calls == 1
    fe = next(s for s in obs.TRACER.spans if s.name == "executor.fused_eval")
    ex = next(s for s in obs.TRACER.spans if s.name == "executor.execute")
    assert fe.parent_sid == ex.sid               # launch nests in execute
    # counters absorbed the ExecStats and the launch accounting agrees
    assert obs.REGISTRY.value("eval.launches") == 1
    assert obs.REGISTRY.value("eval.lanes") == res.stats.scan_compares
    assert obs.REGISTRY.value("exec.scan_compares") == res.stats.scan_compares


def test_traced_indexed_query_has_probe_spans(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    idx = db.SortedIndex.build(ks, table, "v")
    q = db.Range("v", _enc(ks, 5, 4), _enc(ks, 30, 5))
    db.execute(ks, table, q, indexes={"v": idx})            # warm
    with obs.tracing():
        res = db.execute(ks, table, q, indexes={"v": idx})
    names = [s.name for s in obs.TRACER.spans]
    assert "index.search" in names
    search = next(s for s in obs.TRACER.spans if s.name == "index.search")
    assert search.args["probes"] == res.stats.index_compares
    assert obs.REGISTRY.value("index.probes") == res.stats.index_compares
    # one launch per binary-search step, all lanes accounted
    assert obs.REGISTRY.value("eval.launches") > 0
    assert obs.REGISTRY.value("eval.lanes") >= res.stats.index_compares


# ---------------------------------------------------------------------------
# counter reconciliation: per-query stats vs batch totals
# ---------------------------------------------------------------------------

def test_reconcile_scan_batch(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    server = db.QueryServer(ks, table, batch=4)
    qids = [server.submit(db.Range("v", _enc(ks, lo, 10 + lo),
                                   _enc(ks, hi, 50 + hi)))
            for lo, hi in [(3, 9), (5, 26), (8, 97)]]
    qids.append(server.submit(db.Eq("v", _enc(ks, 23, 99))))
    res = server.run()
    b = server.batch_log[-1]
    assert b.eval_calls == 1                      # one fused launch
    assert sum(res[q].stats.scan_compares for q in qids) == b.scan_compares
    assert sum(res[q].stats.index_compares for q in qids) == 0
    for q in qids:                                # share, not a sum term
        assert res[q].stats.eval_calls == 1


def test_reconcile_indexed_batch(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    idx = db.SortedIndex.build(ks, table, "v")
    server = db.QueryServer(ks, table, indexes={"v": idx}, batch=3)
    qids = [server.submit(db.Range("v", _enc(ks, lo, 10 + lo),
                                   _enc(ks, hi, 50 + hi)))
            for lo, hi in [(3, 9), (5, 26), (14, 93)]]
    with obs.tracing():
        res = server.run()
    b = server.batch_log[-1]
    assert b.scan_compares == 0
    assert sum(res[q].stats.index_compares for q in qids) == b.index_compares
    for q in qids:
        assert res[q].stats.index_compares > 0    # every query got its share
    # the metrics layer saw the same totals the stats objects carry
    assert obs.REGISTRY.value("index.probes") == b.index_compares
    assert obs.REGISTRY.value("server.batch_index_compares") == \
        b.index_compares


def test_reconcile_mutation_batch_bills_delta_probes(bfv_engine_ks):
    """After an insert the probe path is base ∪ delta: per-query stats
    must carry BOTH shares and still sum to the batch total."""
    ks = bfv_engine_ks
    table = _table(ks, VALS, name="t_mut")
    idx = db.SortedIndex.build(ks, table, "v")
    server = db.QueryServer(ks, table, indexes={"v": idx}, batch=4)
    server.submit_insert({"v": np.array([7, 50], np.int64)},
                         jax.random.PRNGKey(77))
    server.run()                                  # delta run materialized
    qids = [server.submit(db.Range("v", _enc(ks, 5, 301),
                                   _enc(ks, 60, 302))),
            server.submit(db.Eq("v", _enc(ks, 50, 303)))]
    res = server.run()
    b = server.batch_log[-1]
    assert table.n_delta > 0
    assert sum(res[q].stats.index_compares for q in qids) == b.index_compares
    # both paths billed: each query probed the base index AND the delta run
    base_depth = max(1, (table.n_rows - 1).bit_length())
    for q in qids:
        assert res[q].stats.index_compares > 2 * base_depth
    # answers stay exact across the union probe
    all_vals = np.concatenate([VALS, [7, 50]])
    assert np.array_equal(res[qids[0]].mask,
                          (all_vals >= 5) & (all_vals <= 60))


def test_reconcile_join_batch(bfv_engine_ks):
    ks = bfv_engine_ks
    lt = _table(ks, VALS % 8, name="jl")
    rt = db.Table.from_arrays(ks, "jr", {"k": (VALS[:6] % 8).astype(np.int64)},
                              jax.random.PRNGKey(3))
    left = db.Table.from_arrays(ks, "jl2", {"k": (VALS % 8).astype(np.int64)},
                                jax.random.PRNGKey(4))
    server = db.QueryServer(ks, left, batch=2)
    jid = server.submit_join(db.Join(None, None, on="k"), rt)
    res = server.run()
    b = server.batch_log[-1]
    js = res[jid].stats
    # join-side filter shares fold into stats.left/right; with no WHERE
    # they are zero and the batch only counted the deduped pair grid
    assert js.left.scan_compares + js.right.scan_compares == b.scan_compares
    assert b.pair_compares == js.pair_compares > 0
    want = np.argwhere((VALS % 8)[:, None] == (VALS[:6] % 8)[None, :])
    assert np.array_equal(res[jid].pairs, want)


def test_reconcile_sharded_batch_and_span_nesting(bfv_engine_ks):
    """Sharded server: scan + indexed lanes reconcile, and the shard
    launch spans nest under the batch span (the multi-device CI job
    runs this file on 8 host devices)."""
    ks = bfv_engine_ks
    table = _table(ks, VALS, name="t_sh")
    st = db.ShardedTable.from_table(ks, table, spec=db.ShardSpec.create(2))
    idx = db.ShardedIndex.build(ks, st, "v")
    server = db.ShardedQueryServer(ks, st, indexes={"v": idx}, batch=3)
    qids = [server.submit(db.Range("v", _enc(ks, 3, 401),
                                   _enc(ks, 26, 402))),
            server.submit(db.Eq("v", _enc(ks, 97, 403)))]
    with obs.tracing():
        res = server.run()
    b = server.batch_log[-1]
    assert sum(res[q].stats.index_compares for q in qids) == b.index_compares
    for q in qids:
        assert res[q].stats.index_compares > 0
    spans = obs.TRACER.spans
    batch = next(s for s in spans if s.name == "server.shard_batch")
    nested = [s for s in spans if s.name == "shard.index.search"]
    assert nested, "fan-out search must be traced"
    for s in nested:
        # walk up to the batch span: every shard search nests inside it
        cur = s
        while cur.parent_sid != -1:
            cur = next(p for p in spans if p.sid == cur.parent_sid)
        assert cur.sid == batch.sid
    assert obs.validate_chrome_trace(obs.chrome_trace()) == []


def test_sharded_index_last_probe_counts(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS, name="t_pc")
    st = db.ShardedTable.from_table(ks, table, spec=db.ShardSpec.create(2))
    idx = db.ShardedIndex.build(ks, st, "v")
    from repro.db.index import _stack_cts
    lanes = _stack_cts([_enc(ks, 5, 1), _enc(ks, 26, 2)])
    before = idx.search_compares
    idx.search(ks, lanes, np.array([False, True]))
    assert idx.last_probe_counts.shape == (2,)
    assert int(idx.last_probe_counts.sum()) == idx.search_compares - before


def test_traced_compaction_has_merge_round_spans(bfv_engine_ks):
    """Folding a delta through the merge network traces every round."""
    ks = bfv_engine_ks
    table = _table(ks, VALS, name="t_cmp")
    indexes = {"v": db.SortedIndex.build(ks, table, "v")}
    table.insert(ks, {"v": np.array([7, 50, 2], np.int64)},
                 jax.random.PRNGKey(5))
    with obs.tracing():
        cstats = db.compact(ks, table, indexes)
    names = [s.name for s in obs.TRACER.spans]
    assert "compact" in names and "compact.merge_index" in names
    rounds = [s for s in obs.TRACER.spans if s.name == "merge.round"]
    assert len(rounds) == cstats.merge_rounds > 0
    assert obs.REGISTRY.value("compact.merge_compares") == \
        cstats.merge_compares
    # merge-round compare-swaps land in the launch accounting too
    assert obs.REGISTRY.value("eval.launches") > 0
    assert not table.has_delta


# ---------------------------------------------------------------------------
# tenants and exporters
# ---------------------------------------------------------------------------

def test_per_tenant_attribution(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    server = db.QueryServer(ks, table, batch=2)
    qa = server.submit(db.Eq("v", _enc(ks, 15, 501)), tenant="alice")
    qb = server.submit(db.Range("v", _enc(ks, 3, 502), _enc(ks, 97, 503)),
                       tenant="bob")
    with obs.tracing():
        res = server.run()
    reg = obs.REGISTRY
    assert reg.value("server.queries", tenant="alice") == 1
    assert reg.value("server.queries", tenant="bob") == 1
    assert reg.value("server.compares", tenant="alice") == \
        res[qa].stats.filter_compares
    assert reg.value("server.compares", tenant="bob") == \
        res[qb].stats.filter_compares


def test_metrics_dump_and_bench_fields_from_server(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, VALS)
    server = db.QueryServer(ks, table, batch=1)
    server.submit(db.Eq("v", _enc(ks, 15, 601)))
    with obs.tracing():
        server.run()
        dump = obs.metrics_dump()
        fields = obs.bench_fields()
    assert "metrics" in dump and "jit_signatures" in dump
    assert fields["eval_launches"] >= 1
    assert fields["compare_lanes"] >= table.n_padded
    assert dump["metrics"]["server.batches"] == 1
