"""The always-on serving loop: admission, scheduling, ordering, faults.

Covers the `repro.db.serve_loop.ServeLoop` contract, on the plain AND
sharded servers (the multi-device CI job re-runs this file on 8 host
devices):

  * admission control — per-tenant + total queue caps and tenant ACLs
    produce explicit REJECTED responses, never unbounded queuing;
  * two-class deadline-aware scheduling — point batches draft before
    bulk, bulk never starves, expired requests SHED at batch formation,
    late completions flagged `deadline_missed`;
  * pow2 bucketing + fair-share drafting — batch sizes are powers of
    two, chatty tenants capped, per-tenant FIFO preserved;
  * ordering — mutations are admission-order barriers: every query
    sees exactly the writes admitted before it;
  * answers byte-identical to plain `QueryServer.submit`/`run`;
  * fault isolation — a poisoned plan or transient device error fails
    only its own request; everyone else is answered and obs counters
    stay reconciled;
  * per-tenant counter reconciliation — per-tenant `server.queries` /
    `server.compares` / `serve.*` sums equal loop totals (extends the
    PR 7 reconciliation suite to the loop);
  * jit-cache stability — steady-state `jit.retraces` delta is 0 once
    the pow2 buckets are warm.

Property tests (hypothesis when available, seeded deterministic sweep
otherwise — collection and tier-1 must survive without hypothesis)
drive random arrival sequences through the loop and assert the
no-starvation / FIFO / byte-identical / read-your-admitted-writes
invariants.
"""
import threading
import time

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # collection must survive without hypothesis
    HAVE_HYPOTHESIS = False

from repro import db, obs
from repro.core import encrypt as E
from repro.db import plan as P
from repro.db.serve_loop import (
    BULK, FAILED, OK, PENDING, POINT, REJECTED, SHED, WRITE,
    AdmissionPolicy, Response, ServeLoop,
)

VALS = np.array([3, 14, 15, 9, 26, 5, 35, 8, 97, 93, 23, 84], np.int64)


def _enc(ks, v, seed):
    return E.encrypt(ks, np.int64(int(v)), jax.random.PRNGKey(seed))


def _table(ks, vals=VALS, name="t"):
    return db.Table.from_arrays(ks, name, {"v": np.asarray(vals, np.int64)},
                                jax.random.PRNGKey(2))


# read-only (table, indexes, ciphertext pool) shared across tests — one
# encrypted sort + a handful of encryptions per keyset, not per test
_ENV = {}


def _env(ks):
    if id(ks) not in _ENV:
        table = _table(ks, name="t_loop")
        indexes = {"v": db.SortedIndex.build(ks, table, "v")}
        pool = {int(v): _enc(ks, int(v), 7000 + i)
                for i, v in enumerate(VALS)}
        _ENV[id(ks)] = (table, indexes, pool)
    return _ENV[id(ks)]


def _mk_loop(ks, *, index=True, policy=None, batch=8, clock=time.monotonic,
             **kw):
    table, indexes, pool = _env(ks)
    server = db.QueryServer(ks, table, indexes=indexes if index else {},
                            batch=batch)
    loop = ServeLoop(policy=policy, batch=batch, clock=clock, **kw)
    loop.register("t", server)
    return loop, server, table, pool


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_tenant_queue_cap_rejects_explicitly(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(
        ks, policy=AdmissionPolicy(tenant_queue_cap=2))
    t1 = loop.submit("alice", "t", db.Eq("v", pool[15]))
    t2 = loop.submit("alice", "t", db.Eq("v", pool[26]))
    t3 = loop.submit("alice", "t", db.Eq("v", pool[35]))
    assert loop.response(t1).status == PENDING
    assert loop.response(t2).status == PENDING
    r3 = loop.response(t3)
    assert r3.status == REJECTED and r3.done
    assert "queue full" in r3.error
    assert loop.stats.rejected == 1 and loop.stats.admitted == 2
    assert loop.queue_depth("alice") == 2      # the reject never queued
    res = loop.run_until_idle()
    assert res[t1].status == OK and res[t2].status == OK
    assert res[t3].status == REJECTED          # terminal states persist


def test_total_queue_cap_rejects_across_tenants(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(
        ks, policy=AdmissionPolicy(total_queue_cap=2))
    loop.submit("alice", "t", db.Eq("v", pool[15]))
    loop.submit("bob", "t", db.Eq("v", pool[26]))
    t3 = loop.submit("carol", "t", db.Eq("v", pool[35]))
    r3 = loop.response(t3)
    assert r3.status == REJECTED and "loop queue full" in r3.error


def test_tenant_acl_gates_per_tenant_tables(bfv_engine_ks):
    """Per-tenant KeySets ride per-tenant registrations: a table ACLed
    to alice rejects bob at admission, before any ciphertext touches
    bob's request."""
    ks = bfv_engine_ks
    table, indexes, pool = _env(ks)
    loop = ServeLoop()
    loop.register("alice_t", db.QueryServer(ks, table, indexes=indexes),
                  tenants=("alice",))
    ta = loop.submit("alice", "alice_t", db.Eq("v", pool[15]))
    tb = loop.submit("bob", "alice_t", db.Eq("v", pool[15]))
    rb = loop.response(tb)
    assert rb.status == REJECTED and "not authorized" in rb.error
    res = loop.run_until_idle()
    assert res[ta].status == OK
    assert len(res[ta].result.row_ids) == 1


def test_unknown_table_raises(bfv_engine_ks):
    loop = ServeLoop()
    with pytest.raises(KeyError):
        loop.submit("alice", "nope", db.Eq("v", None))


def test_join_on_sharded_server_rejected_explicitly(bfv_engine_ks):
    ks = bfv_engine_ks
    table, _, pool = _env(ks)
    stable = db.ShardedTable.from_table(ks, table,
                                        spec=db.ShardSpec.create(2))
    loop = ServeLoop()
    loop.register("sh", db.ShardedQueryServer(ks, stable))
    t = loop.submit_join("alice", "sh", db.Join(None, None, on="v"), table)
    r = loop.response(t)
    assert r.status == REJECTED and "does not support joins" in r.error
    assert loop.queue_depth() == 0 and loop.stats.admitted == 0
    # the rejection is atomic at admission: never enqueued, never
    # drafted, terminal counters reconcile (no double counting)
    assert loop.stats.submitted == loop.stats.rejected == 1
    assert loop.stats.failed == 0 and loop.batch_shapes == []


def test_unknown_klass_override_raises(bfv_engine_ks):
    """A klass outside {point, bulk} would pend forever (no pump drafts
    it) — submit() refuses it up front, admitting nothing."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    with pytest.raises(ValueError, match="klass"):
        loop.submit("a", "t", db.Eq("v", pool[15]), klass="interactive")
    assert loop.stats.submitted == 0 and loop.queue_depth() == 0
    loop.submit("a", "t", db.Eq("v", pool[15]), klass=BULK)  # valid override
    assert all(r.status == OK
               for r in loop.run_until_idle().values())


# ---------------------------------------------------------------------------
# classification + scheduling
# ---------------------------------------------------------------------------

def test_classification_point_vs_bulk(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    tp = loop.submit("a", "t", db.Eq("v", pool[15]))
    tr = loop.submit("a", "t", P.Query(where=db.Range("v", pool[3],
                                                      pool[26]),
                                       top_k=db.TopK("v", 2)))
    ts = loop.submit("a", "t", P.Query())                   # select-all scan
    to = loop.submit("a", "t", db.Eq("v", pool[15]), klass=BULK)
    assert loop.response(tp).klass == POINT
    assert loop.response(tr).klass == BULK     # top-k pays a sort network
    assert loop.response(ts).klass == BULK     # select-all = full scan
    assert loop.response(to).klass == BULK     # explicit override wins
    loop.run_until_idle()


def test_unindexed_leaf_classifies_bulk(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, index=False)
    t = loop.submit("a", "t", db.Eq("v", pool[15]))
    assert loop.response(t).klass == BULK


def test_point_batch_drafts_before_bulk(bfv_engine_ks):
    """The deadline-sensitive class never waits behind a scan: even
    when the bulk request was submitted FIRST, the pump runs the point
    batch first."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    tb = loop.submit("a", "t", db.Range("v", pool[3], pool[97]),
                     klass=BULK)
    tp = loop.submit("a", "t", db.Eq("v", pool[15]))
    res = loop.run_until_idle()
    assert res[tb].status == OK and res[tp].status == OK
    klasses = [k for (_, k, _) in loop.batch_shapes]
    assert klasses == [POINT, BULK]
    assert res[tp].start_t <= res[tb].start_t


def test_bulk_is_not_starved_by_point_traffic(bfv_engine_ks):
    """Every pump drafts one bulk batch too — a scan admitted behind a
    pile of point lookups completes within the first pump."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, batch=4)
    for i in range(8):
        loop.submit("a", "t", db.Eq("v", pool[int(VALS[i % 12])]))
    tb = loop.submit("a", "t", db.Range("v", pool[3], pool[97]),
                     klass=BULK)
    loop.pump()
    assert loop.response(tb).status == OK
    res = loop.run_until_idle()
    assert all(r.status == OK for r in res.values())


def test_pow2_bucketing_of_batch_sizes(bfv_engine_ks):
    """7 pending requests draft as 4 + 2 + 1 — every launch shape comes
    from the closed pow2 set, so the jit cache stays hot."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, batch=8)
    for i in range(7):
        loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
    res = loop.run_until_idle()
    assert [s for (_, _, s) in loop.batch_shapes] == [4, 2, 1]
    assert all(r.status == OK for r in res.values())


def test_fair_share_caps_chatty_tenant(bfv_engine_ks):
    """fair_share=2: a tenant with 6 pending gets at most 2 slots of a
    contended batch, so the quiet tenant's 2 requests ride the FIRST
    batch instead of queuing behind all 6."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(
        ks, policy=AdmissionPolicy(fair_share=2), batch=8)
    chatty = [loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
              for i in range(6)]
    quiet = [loop.submit("b", "t", db.Eq("v", pool[97])),
             loop.submit("b", "t", db.Eq("v", pool[93]))]
    loop.pump()
    assert all(loop.response(t).status == OK for t in quiet)
    assert sum(loop.response(t).status == OK for t in chatty) == 2
    res = loop.run_until_idle()
    assert all(r.status == OK for r in res.values())


def test_deadline_shed_before_execution(bfv_engine_ks):
    """A request whose deadline passed while queued is SHED at batch
    formation — the engine never runs it."""
    ks = bfv_engine_ks
    clock = FakeClock()
    loop, server, _, pool = _mk_loop(ks, clock=clock)
    t = loop.submit("a", "t", db.Eq("v", pool[15]), deadline=5.0)
    clock.advance(6.0)
    loop.pump()
    r = loop.response(t)
    assert r.status == SHED and r.done and "deadline" in r.error
    assert loop.stats.shed == 1 and loop.stats.served == 0
    assert server.batch_log == []              # nothing reached the engine


def test_deadline_miss_flagged_on_late_completion(bfv_engine_ks):
    """A request drafted in time but finished late is answered, with
    `deadline_missed=True` and a per-tenant deadline-miss count."""
    ks = bfv_engine_ks
    clock = FakeClock()
    loop, server, _, pool = _mk_loop(ks, clock=clock)
    orig = server.run

    def slow_run():
        clock.advance(10.0)
        return orig()

    server.run = slow_run
    t = loop.submit("a", "t", db.Eq("v", pool[15]), deadline=5.0)
    with obs.tracing():
        loop.pump()
    r = loop.response(t)
    assert r.status == OK and r.deadline_missed
    assert len(r.result.row_ids) == 1          # still a real answer
    assert loop.stats.deadline_miss == 1
    assert obs.REGISTRY.value("serve.deadline_miss", tenant="a") == 1


def test_writes_are_never_shed(bfv_engine_ks):
    """Shedding an admitted write would break read-your-admitted-writes
    for every later query, so deadlines do not shed the write class."""
    ks = bfv_engine_ks
    clock = FakeClock()
    table = _table(ks, name="t_ws")
    loop = ServeLoop(clock=clock)
    loop.register("t", db.QueryServer(ks, table))
    t = loop.submit_insert("a", "t", {"v": np.array([41], np.int64)},
                           jax.random.PRNGKey(9), deadline=1.0)
    clock.advance(5.0)
    res = loop.run_until_idle()
    assert res[t].status == OK and res[t].result.kind == "insert"
    assert loop.stats.shed == 0


# ---------------------------------------------------------------------------
# ordering: mutations are admission-order barriers
# ---------------------------------------------------------------------------

def test_query_sees_exactly_the_writes_admitted_before_it(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, name="t_rw")
    loop = ServeLoop()
    loop.register("t", db.QueryServer(ks, table))
    ct = _enc(ks, 41, 901)
    q_before = loop.submit("a", "t", db.Eq("v", ct))
    loop.submit_insert("a", "t", {"v": np.array([41], np.int64)},
                       jax.random.PRNGKey(10))
    q_after = loop.submit("a", "t", db.Eq("v", ct))
    res = loop.run_until_idle()
    assert len(res[q_before].result.row_ids) == 0
    assert len(res[q_after].result.row_ids) == 1


def test_write_barrier_splits_batches(bfv_engine_ks):
    """query, write, query admitted in order run as three separate
    drains — the two-class reordering never crosses a barrier."""
    ks = bfv_engine_ks
    table = _table(ks, name="t_bar")
    loop = ServeLoop()
    loop.register("t", db.QueryServer(ks, table))
    loop.submit("a", "t", db.Eq("v", _enc(ks, 15, 902)))
    loop.submit_insert("a", "t", {"v": np.array([55], np.int64)},
                       jax.random.PRNGKey(11))
    loop.submit("a", "t", db.Eq("v", _enc(ks, 55, 903)))
    res = loop.run_until_idle()
    assert [(k, s) for (_, k, s) in loop.batch_shapes] == \
        [(BULK, 1), (WRITE, 1), (BULK, 1)]
    assert all(r.status == OK for r in res.values())


def test_fifo_within_tenant_class(bfv_engine_ks):
    """Within one (tenant, class) the engine receives requests in
    submit order, across multiple drafted batches."""
    ks = bfv_engine_ks
    loop, server, _, pool = _mk_loop(ks, batch=2)
    received = []
    orig = server.submit

    def recording_submit(query, *, tenant=None):
        received.append(id(query))
        return orig(query, tenant=tenant)

    server.submit = recording_submit
    submitted = []
    for i in range(5):
        q = P.Query(where=db.Eq("v", pool[int(VALS[i])]))
        submitted.append(id(q))
        loop.submit("a", "t", q)
    res = loop.run_until_idle()
    assert received == submitted
    assert all(r.status == OK for r in res.values())


# ---------------------------------------------------------------------------
# answers byte-identical to the plain server
# ---------------------------------------------------------------------------

def test_answers_match_plain_query_server(bfv_engine_ks):
    ks = bfv_engine_ks
    table, indexes, pool = _env(ks)
    plans = [P.Query(where=db.Eq("v", pool[15])),
             P.Query(where=db.Range("v", pool[5], pool[35])),
             P.Query(where=db.Or(db.Eq("v", pool[97]),
                                 db.Range("v", pool[3], pool[9])))]
    loop, _, _, _ = _mk_loop(ks)
    tickets = [loop.submit("a", "t", q) for q in plans]
    res = loop.run_until_idle()
    plain = db.QueryServer(ks, table, indexes=indexes, batch=len(plans))
    qids = [plain.submit(q) for q in plans]
    want = plain.run()
    for t, q in zip(tickets, qids):
        np.testing.assert_array_equal(res[t].result.row_ids,
                                      want[q].row_ids)
        np.testing.assert_array_equal(res[t].result.mask, want[q].mask)


def test_join_through_loop_matches_execute_join(bfv_engine_ks):
    ks = bfv_engine_ks
    table, _, pool = _env(ks)
    right = db.Table.from_arrays(
        ks, "t_r", {"v": VALS[:6]}, jax.random.PRNGKey(3))
    j = db.Join(None, None, on="v")
    loop, _, _, _ = _mk_loop(ks)
    t = loop.submit_join("a", "t", j, right, strategy="nested")
    res = loop.run_until_idle()
    want = db.execute_join(ks, table, right, j, strategy="nested")
    np.testing.assert_array_equal(res[t].result.pairs, want.pairs)
    assert res[t].klass == BULK


def test_sharded_loop_matches_plain(bfv_engine_ks):
    """The loop over a ShardedQueryServer answers exactly like the
    plain server over the same rows (runs at 1 and 8 devices)."""
    ks = bfv_engine_ks
    table, indexes, pool = _env(ks)
    stable = db.ShardedTable.from_table(ks, table,
                                        spec=db.ShardSpec.create(2))
    sidx = {"v": db.ShardedIndex.build(ks, stable, "v")}
    loop = ServeLoop()
    loop.register("sh", db.ShardedQueryServer(ks, stable, indexes=sidx))
    plans = [P.Query(where=db.Eq("v", pool[15])),
             P.Query(where=db.Range("v", pool[5], pool[35]))]
    tickets = [loop.submit("a", "sh", q) for q in plans]
    res = loop.run_until_idle()
    plain = db.QueryServer(ks, table, indexes=indexes, batch=2)
    qids = [plain.submit(q) for q in plans]
    want = plain.run()
    for t, q in zip(tickets, qids):
        got_rows = np.sort(np.asarray(res[t].result.row_ids))
        np.testing.assert_array_equal(got_rows,
                                      np.sort(want[q].row_ids))
    assert all(loop.response(t).klass == POINT for t in tickets)


# ---------------------------------------------------------------------------
# fault isolation
# ---------------------------------------------------------------------------

def test_poisoned_plan_fails_alone(bfv_engine_ks):
    """A plan naming a nonexistent column fails ITS request; the other
    requests in the same drafted batch are still answered and the loop
    keeps serving afterwards."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    good1 = loop.submit("a", "t", db.Range("v", pool[3], pool[97]),
                        klass=BULK)
    bad = loop.submit("b", "t", db.Eq("nope", pool[15]))
    good2 = loop.submit("a", "t", db.Range("v", pool[5], pool[35]),
                        klass=BULK)
    res = loop.run_until_idle()
    assert res[bad].status == FAILED and "nope" in res[bad].error
    assert res[good1].status == OK and res[good2].status == OK
    assert len(res[good1].result.row_ids) == len(VALS)
    assert loop.stats.failed == 1 and loop.stats.served == 2
    later = loop.submit("b", "t", db.Eq("v", pool[26]))
    assert loop.run_until_idle()[later].status == OK


def test_transient_device_error_recovers_everyone(bfv_engine_ks):
    """A device error that poisons one collective drain but not the
    per-request retries loses NO requests."""
    ks = bfv_engine_ks
    from repro.db import executor as X
    loop, _, _, pool = _mk_loop(ks, index=False)
    tickets = [loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
               for i in range(4)]
    orig, boom = X.fused_eval, {"left": 1}

    def flaky(*args, **kw):
        if boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("XLA device lost (injected)")
        return orig(*args, **kw)

    X.fused_eval = flaky
    try:
        res = loop.run_until_idle()
    finally:
        X.fused_eval = orig
    assert all(res[t].status == OK for t in tickets)
    assert loop.stats.failed == 0 and loop.stats.served == 4


def test_persistent_fault_isolates_and_counters_reconcile(bfv_engine_ks):
    """With obs live, a batch where one request keeps failing bills
    exactly the served requests: per-tenant server.queries sums equal
    loop served totals, serve.failed equals loop failed totals."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    ga = loop.submit("alice", "t", db.Range("v", pool[3], pool[97]),
                     klass=BULK)
    bb = loop.submit("bob", "t", db.Eq("nope", pool[15]))
    gb = loop.submit("bob", "t", db.Range("v", pool[5], pool[35]),
                     klass=BULK)
    with obs.tracing():
        res = loop.run_until_idle()
        reg = obs.REGISTRY
        billed = (reg.value("server.queries", tenant="alice")
                  + reg.value("server.queries", tenant="bob"))
        assert billed == loop.stats.served == 2
        assert reg.value("serve.failed", tenant="bob") == \
            loop.stats.failed == 1
    assert res[ga].status == OK and res[gb].status == OK
    assert res[bb].status == FAILED


def test_failed_write_does_not_poison_loop(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, name="t_fw")
    loop = ServeLoop()
    loop.register("t", db.QueryServer(ks, table))
    bad = loop.submit_insert("a", "t", {"wrong_col": np.array([1])},
                             jax.random.PRNGKey(12))
    good = loop.submit("a", "t", db.Eq("v", _enc(ks, 15, 904)))
    res = loop.run_until_idle()
    assert res[bad].status == FAILED and res[bad].error
    assert res[good].status == OK


# ---------------------------------------------------------------------------
# per-tenant counter reconciliation under the loop (extends PR 7 suite)
# ---------------------------------------------------------------------------

def _reconcile(loop, res, tenants):
    """Per-tenant registry counters must sum to loop totals."""
    reg = obs.REGISTRY
    served_reads = sum(1 for r in res.values()
                       if r.status == OK and r.klass != WRITE)
    assert sum(reg.value("server.queries", tenant=t)
               for t in tenants) == served_reads
    for t in tenants:
        want = sum(r.result.stats.filter_compares for r in res.values()
                   if r.tenant == t and r.status == OK
                   and r.klass != WRITE)
        assert reg.value("server.compares", tenant=t) == want
    assert sum(reg.value("serve.shed", tenant=t)
               for t in tenants) == loop.stats.shed
    assert sum(reg.value("serve.deadline_miss", tenant=t)
               for t in tenants) == loop.stats.deadline_miss


def test_per_tenant_reconciliation_plain_server(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, name="t_rec")
    indexes = {"v": db.SortedIndex.build(ks, table, "v")}
    loop = ServeLoop()
    loop.register("t", db.QueryServer(ks, table, indexes=indexes))
    with obs.tracing():
        loop.submit("alice", "t", db.Eq("v", _enc(ks, 15, 905)))
        loop.submit("bob", "t", db.Range("v", _enc(ks, 3, 906),
                                         _enc(ks, 97, 907)), klass=BULK)
        loop.submit_insert("alice", "t", {"v": np.array([60], np.int64)},
                           jax.random.PRNGKey(13))
        loop.submit("bob", "t", db.Eq("v", _enc(ks, 60, 908)))
        res = loop.run_until_idle()
        _reconcile(loop, res, ("alice", "bob"))
    assert all(r.status == OK for r in res.values())


def test_per_tenant_reconciliation_sharded_server(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, name="t_recs")
    stable = db.ShardedTable.from_table(ks, table,
                                        spec=db.ShardSpec.create(2))
    sidx = {"v": db.ShardedIndex.build(ks, stable, "v")}
    loop = ServeLoop()
    loop.register("sh", db.ShardedQueryServer(ks, stable, indexes=sidx))
    with obs.tracing():
        loop.submit("alice", "sh", db.Eq("v", _enc(ks, 15, 909)))
        loop.submit("bob", "sh", db.Range("v", _enc(ks, 3, 910),
                                          _enc(ks, 97, 911)), klass=BULK)
        loop.submit("alice", "sh", db.Eq("v", _enc(ks, 26, 912)))
        res = loop.run_until_idle()
        _reconcile(loop, res, ("alice", "bob"))
    assert all(r.status == OK for r in res.values())


def test_shed_and_miss_reconcile_per_tenant(bfv_engine_ks):
    ks = bfv_engine_ks
    clock = FakeClock()
    loop, _, _, pool = _mk_loop(ks, clock=clock)
    with obs.tracing():
        loop.submit("alice", "t", db.Eq("v", pool[15]), deadline=1.0)
        loop.submit("bob", "t", db.Eq("v", pool[26]))
        clock.advance(2.0)
        res = loop.run_until_idle()
        _reconcile(loop, res, ("alice", "bob"))
    assert loop.stats.shed == 1


# ---------------------------------------------------------------------------
# obs integration + jit-cache stability
# ---------------------------------------------------------------------------

def test_queue_depth_wait_and_spans_observed(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    with obs.tracing():
        loop.submit("a", "t", db.Eq("v", pool[15]))
        loop.submit("a", "t", db.Range("v", pool[3], pool[97]),
                    klass=BULK)
        loop.run_until_idle()
        dump = obs.metrics_dump()["metrics"]
        assert any(k.startswith("serve.queue_depth") for k in dump)
        assert any(k.startswith("serve.queue_wait_s") for k in dump)
        spans = {s.name for s in obs.TRACER.spans}
        assert "serve.pump" in spans and "serve.batch" in spans
        batch_spans = [s for s in obs.TRACER.spans
                       if s.name == "serve.batch"]
        assert {s.args["klass"] for s in batch_spans} == {POINT, BULK}
        assert obs.validate_chrome_trace(obs.chrome_trace()) == []


def test_batch_spans_carry_their_requests(bfv_engine_ks):
    """Each `serve.batch` span names the tickets it drafted (a write's,
    its one ticket) and each `server.batch` under it the server's qids,
    so a request's spans share an identifier."""
    ks = bfv_engine_ks
    _, _, pool = _env(ks)
    loop = ServeLoop(batch=4)
    loop.register("t", db.QueryServer(ks, _table(ks, name="t_ids"),
                                      batch=4))
    reads = [loop.submit("a", "t", db.Eq("v", pool[int(v)]))
             for v in VALS[:3]]
    w = loop.submit_insert("a", "t", {"v": np.array([41], np.int64)},
                           jax.random.PRNGKey(9))
    reads.append(loop.submit("a", "t", db.Eq("v", pool[15])))
    with obs.tracing():
        res = loop.run_until_idle()
        spans = list(obs.TRACER.spans)
    assert all(res[t].status == OK for t in reads + [w])
    batches = [s for s in spans if s.name == "serve.batch"]
    assert sorted(t for s in batches for t in s.args["tickets"]) == \
        sorted(reads + [w])
    assert [s.args["tickets"] for s in batches
            if s.args["klass"] == WRITE] == [[w]]
    for s in batches:
        assert len(s.args["tickets"]) == s.args["size"]
        if s.args["klass"] != WRITE:
            kids = [k for k in spans if k.parent_sid == s.sid
                    and k.name == "server.batch"]
            assert sum(len(k.args["qids"]) for k in kids) == s.args["size"]
    doc = obs.chrome_trace()
    assert obs.validate_chrome_trace(doc) == []
    assert any(ev["args"].get("tickets") == [w]
               for ev in doc["traceEvents"])


def test_jit_retraces_zero_in_steady_state(bfv_engine_ks):
    """Once a warmup wave has visited every pow2 bucket, an identical
    steady-state wave adds ZERO jit retraces — the bucketing's whole
    point."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, batch=4)

    def wave():
        for i in range(7):
            loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
        loop.run_until_idle()

    with obs.tracing():
        wave()                                     # warm 4/2/1 buckets
        mark = obs.REGISTRY.value("jit.retraces")
        wave()                                     # steady state
        assert obs.REGISTRY.value("jit.retraces") == mark


def test_background_thread_serves_and_stops(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    loop.start(interval_s=0.001)
    try:
        tickets = [loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
                   for i in range(3)]
        deadline = time.monotonic() + 120.0
        while (any(not loop.response(t).done for t in tickets)
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        loop.stop()
    assert all(loop.response(t).status == OK for t in tickets)
    assert loop._thread is None                   # stop() joined it


def test_run_until_idle_resolves_everything(bfv_engine_ks):
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, batch=2)
    for i in range(5):
        loop.submit("t%d" % (i % 3), "t", db.Eq("v", pool[int(VALS[i])]))
    res = loop.run_until_idle()
    assert loop.queue_depth() == 0
    assert all(r.done for r in res.values())
    assert loop.stats.served == 5


# ---------------------------------------------------------------------------
# bounded response retention (the always-on mode must not leak)
# ---------------------------------------------------------------------------

def test_terminal_responses_bounded_by_max_responses(bfv_engine_ks):
    """Only the `max_responses` most recent TERMINAL responses stay
    readable — older ones evict oldest-first, so a continuous stream
    cannot grow loop memory without bound.  Stats still count every
    request."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks, max_responses=2)
    tks = [loop.submit("a", "t", db.Eq("v", pool[int(VALS[i])]))
           for i in range(4)]
    res = loop.run_until_idle()
    assert set(res) == set(tks[2:])            # evicted oldest-first
    for t in tks[:2]:
        with pytest.raises(KeyError):
            loop.response(t)
    assert all(res[t].status == OK for t in tks[2:])
    assert loop.stats.served == 4
    assert len(loop.batch_shapes) <= 2         # shapes bounded too


def test_forget_releases_terminal_responses(bfv_engine_ks):
    """Continuous-stream clients ack results as they consume them:
    forget() releases a terminal response eagerly, refuses PENDING
    tickets, and is a no-op on unknown/already-released ones."""
    ks = bfv_engine_ks
    loop, _, _, pool = _mk_loop(ks)
    t1 = loop.submit("a", "t", db.Eq("v", pool[15]))
    loop.run_until_idle()
    t2 = loop.submit("a", "t", db.Eq("v", pool[26]))
    with pytest.raises(ValueError):
        loop.forget(t2)                        # still PENDING
    loop.run_until_idle()
    r = loop.forget(t1)
    assert r.status == OK
    assert loop.forget(t1) is None             # already released
    with pytest.raises(KeyError):
        loop.response(t1)
    assert loop.response(t2).status == OK      # unacked ticket retained


# ---------------------------------------------------------------------------
# property tests: random arrival sequences (hypothesis / seeded sweep)
# ---------------------------------------------------------------------------

def _check_stream_invariants(ks, arrivals):
    """Drive one random arrival sequence; assert no starvation, FIFO
    within (tenant, class), and answers identical to the plain server.

    `arrivals` is a list of (tenant#, value#) pairs; value# indexes the
    shared VALS lattice and odd value#s submit as explicit bulk so both
    classes interleave."""
    table, indexes, pool = _env(ks)
    server = db.QueryServer(ks, table, indexes=indexes)
    loop = ServeLoop(batch=4)
    loop.register("t", server)
    received = []
    orig = server.submit

    def recording(query, *, tenant=None):
        received.append((tenant, id(query)))
        return orig(query, tenant=tenant)

    server.submit = recording
    plain = db.QueryServer(ks, table, indexes=indexes, batch=4)
    order = {}
    tickets = []
    for tn, vi in arrivals:
        tenant = "t%d" % tn
        v = int(VALS[vi % len(VALS)])
        q = P.Query(where=db.Eq("v", pool[v]))
        klass = BULK if vi % 2 else None
        tk = loop.submit(tenant, "t", q, klass=klass)
        key = (tenant, loop.response(tk).klass)
        order.setdefault(key, []).append(id(q))
        tickets.append((tk, plain.submit(q)))
    res = loop.run_until_idle()
    # no starvation: every admitted request reached a terminal answer
    assert all(r.done for r in res.values())
    assert loop.stats.served == len(arrivals)
    # FIFO within (tenant, class): the engine received each pair's
    # requests in submit order
    for (tenant, klass), ids in order.items():
        got = [qid for (tn2, qid) in received
               if tn2 == tenant and qid in set(ids)]
        assert got == ids
    # byte-identical to the plain server
    want = plain.run()
    for tk, qid in tickets:
        np.testing.assert_array_equal(res[tk].result.row_ids,
                                      want[qid].row_ids)
        np.testing.assert_array_equal(res[tk].result.mask,
                                      want[qid].mask)


def _check_writes_see_model(ks, script, seed):
    """Random query/insert interleave on a FRESH table: every query's
    match count equals a plaintext model applied in admission order."""
    base = [3, 14, 15, 9]
    table = db.Table.from_arrays(
        ks, "t_prop", {"v": np.asarray(base, np.int64)},
        jax.random.PRNGKey(seed % (1 << 30)))
    loop = ServeLoop(batch=4)
    loop.register("t", db.QueryServer(ks, table))
    model = list(base)
    probe = 41
    ct = _enc(ks, probe, seed % (1 << 30) + 1)
    expect = {}
    for i, op in enumerate(script):
        if op:                    # insert one more matching row
            loop.submit_insert("a", "t",
                               {"v": np.array([probe], np.int64)},
                               jax.random.PRNGKey(seed + i + 2))
            model.append(probe)
        else:
            tk = loop.submit("a", "t", db.Eq("v", ct))
            expect[tk] = sum(1 for v in model if v == probe)
    res = loop.run_until_idle()
    for tk, want in expect.items():
        assert len(res[tk].result.row_ids) == want


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(arrivals=st.lists(
        st.tuples(st.integers(0, 2), st.integers(0, 11)),
        min_size=1, max_size=12))
    def test_stream_invariants_property(bfv_engine_ks, arrivals):
        _check_stream_invariants(bfv_engine_ks, arrivals)

    @settings(max_examples=6, deadline=None)
    @given(script=st.lists(st.booleans(), min_size=1, max_size=5),
           seed=st.integers(0, 2**20))
    def test_queries_see_admitted_writes_property(bfv_engine_ks, script,
                                                  seed):
        _check_writes_see_model(bfv_engine_ks, script, seed)
else:
    # deterministic fallback sweep: same checkers, seeded rng fixture —
    # failures replay from the test name alone (see conftest.rng)
    def test_stream_invariants_property(bfv_engine_ks, rng):
        for _ in range(4):
            n = int(rng.integers(1, 13))
            arrivals = [(int(rng.integers(0, 3)), int(rng.integers(0, 12)))
                        for _ in range(n)]
            _check_stream_invariants(bfv_engine_ks, arrivals)

    def test_queries_see_admitted_writes_property(bfv_engine_ks, rng):
        for _ in range(3):
            n = int(rng.integers(1, 6))
            script = [bool(rng.integers(0, 2)) for _ in range(n)]
            _check_writes_see_model(bfv_engine_ks, script,
                                    int(rng.integers(1 << 20)))


# ---------------------------------------------------------------------------
# public fault-recovery API on the servers (the loop uses no internals)
# ---------------------------------------------------------------------------

def test_clear_queue_and_batch_size_public_api(bfv_engine_ks):
    """The loop's fault recovery rides public server API: clear_queue()
    drops queued requests, batch_size() restores the configured size
    even when the drain raises — on BOTH server flavors."""
    ks = bfv_engine_ks
    table, indexes, pool = _env(ks)
    server = db.QueryServer(ks, table, indexes=indexes, batch=3)
    server.submit(db.Eq("v", pool[15]))
    server.submit(db.Eq("v", pool[26]))
    assert server.clear_queue() == 2
    assert server.run() == {}                  # nothing left to drain
    with server.batch_size(8):
        assert server.batch == 8
    assert server.batch == 3
    with pytest.raises(RuntimeError, match="boom"):
        with server.batch_size(5):
            raise RuntimeError("boom")
    assert server.batch == 3                   # restored on failure too

    stable = db.ShardedTable.from_table(ks, table,
                                        spec=db.ShardSpec.create(2))
    sserver = db.ShardedQueryServer(ks, stable, batch=3)
    sserver.submit(db.Eq("v", pool[15]))
    assert sserver.clear_queue() == 1 and sserver.run() == {}
    with sserver.batch_size(4):
        assert sserver.batch == 4
    assert sserver.batch == 3


# ---------------------------------------------------------------------------
# satellite fix: server-scope sort-merge run cache
# ---------------------------------------------------------------------------

def test_sorted_run_cache_survives_batches_until_mutation(bfv_engine_ks):
    """Two consecutive batches sort-merge-joining on the same
    un-indexed column build the O(n log² n) run ONCE; a mutation bumps
    the table version and invalidates the cache."""
    ks = bfv_engine_ks
    table = _table(ks, VALS[:8], name="t_rc")
    lidx = {"v": db.SortedIndex.build(ks, table, "v")}
    right = db.Table.from_arrays(ks, "t_rc_r", {"v": VALS[:6]},
                                 jax.random.PRNGKey(4))
    server = db.QueryServer(ks, table, indexes=lidx, batch=1)
    j = db.Join(None, None, on="v")
    # batch 1: right side has no index -> run built on the fly
    q1 = server.submit_join(j, right, strategy="sort_merge")
    r1 = server.run()[q1]
    assert r1.stats.build_compares > 0
    # batch 2: same (table, column) -> cached run, zero build compares
    q2 = server.submit_join(j, right, strategy="sort_merge")
    r2 = server.run()[q2]
    assert r2.stats.build_compares == 0
    np.testing.assert_array_equal(r1.pairs, r2.pairs)
    # a mutation on the right table invalidates ITS cache entry
    right.insert(ks, {"v": np.array([3], np.int64)},
                 jax.random.PRNGKey(5))
    from repro.db.delta import compact as _compact
    _compact(ks, right, {})                # joins refuse pending deltas
    q3 = server.submit_join(j, right, strategy="sort_merge")
    r3 = server.run()[q3]
    assert r3.stats.build_compares > 0
    assert len(r3.pairs) > len(r2.pairs)   # the new row joined


def test_run_cache_recycled_table_id_cannot_alias(bfv_engine_ks):
    """A dead transient table's memoized run must never serve a fresh
    table that recycled its id(): fresh tables all start at version 0,
    so the version check alone would pass — the weakref identity guard
    refuses the hit and the run is rebuilt for the right rows."""
    ks = bfv_engine_ks
    table = _table(ks, VALS[:8], name="t_alias")
    lidx = {"v": db.SortedIndex.build(ks, table, "v")}
    server = db.QueryServer(ks, table, indexes=lidx, batch=1)
    j = db.Join(None, None, on="v")
    decoy = db.Table.from_arrays(          # rows that match NOTHING
        ks, "t_alias_d", {"v": np.full(6, 61, np.int64)},
        jax.random.PRNGKey(6))
    server.submit_join(j, decoy, strategy="sort_merge")
    server.run()
    stale = server._run_cache[(id(decoy), "v")]
    right = db.Table.from_arrays(ks, "t_alias_r", {"v": VALS[:6]},
                                 jax.random.PRNGKey(7))
    assert right.version == decoy.version == 0
    # simulate CPython id reuse: plant the decoy's entry under the
    # fresh table's id — only the weakref referent tells them apart
    server._run_cache[(id(right), "v")] = stale
    q = server.submit_join(j, right, strategy="sort_merge")
    r = server.run()[q]
    assert r.stats.build_compares > 0      # rebuilt, not aliased
    clean = db.QueryServer(ks, table, indexes=lidx, batch=1)
    qc = clean.submit_join(j, right, strategy="sort_merge")
    want = clean.run()[qc]
    np.testing.assert_array_equal(r.pairs, want.pairs)
    assert len(r.pairs) > 0                # the decoy's run had 0 matches


def test_run_cache_releases_dead_tables(bfv_engine_ks):
    """When a transient right table dies, the weakref callback evicts
    its entry — the server-scope cache cannot accumulate dead runs
    under an always-on request stream."""
    import gc
    ks = bfv_engine_ks
    table = _table(ks, VALS[:8], name="t_gcrc")
    lidx = {"v": db.SortedIndex.build(ks, table, "v")}
    server = db.QueryServer(ks, table, indexes=lidx, batch=1)
    j = db.Join(None, None, on="v")
    right = db.Table.from_arrays(ks, "t_gcrc_r", {"v": VALS[:6]},
                                 jax.random.PRNGKey(8))
    key = (id(right), "v")
    server.submit_join(j, right, strategy="sort_merge")
    server.run()
    assert key in server._run_cache
    del right
    gc.collect()
    assert key not in server._run_cache
