"""Encrypted write path: delta runs, tombstones, compaction.

Covers the mutation lifecycle end to end on both schemes:

  * pad geometry edge cases unlocked for the write path — `next_pow2(0)`
    is 1 (an empty column pads to ONE slot, not two), `Table.empty`,
    insert into an empty table;
  * union reads: scans and index probes answer over base ∪ delta with
    the delta run riding the SAME fused launch (scan) or a per-run
    binary search (index), including duplicate keys split across base
    and delta and ε-band predicates under ckks;
  * deletes as host-side tombstones (delete-all still answers),
    updates as tombstone + re-insert;
  * per-column key derivation by name (crc32), not dict insertion
    order — base and delta ingests agree regardless of column order;
  * compaction through the merge network: answers unchanged, global ids
    stable, merge compares strictly below the from-scratch rebuild at
    realistic sizes;
  * shard invariance S ∈ {1..4}: the mutated + compacted view decrypts
    identically to a from-scratch table holding the same rows;
  * the servers' mutation queues: FIFO visibility (a query sees exactly
    the writes submitted before it) and cooperative compaction under a
    live query load.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import db
from repro.core import encrypt as E
from repro.core.ckks import equality_tolerance
from repro.core.compare import bitonic_compare_count, next_pow2
from repro.db import plan as P
from repro.db.table import Table, column_key, pad_rows_pow2

GRID = 0.25        # ckks float grid (>> test-ckks equality tolerance)
EPS_BAND = 0.3     # ε-band capturing exactly the ±1-grid-step neighbors


def _is_ckks(ks) -> bool:
    return ks.params.profile.scheme == "ckks"


def _vals(ks, ints) -> np.ndarray:
    ints = np.asarray(ints)
    if _is_ckks(ks):
        return ints.astype(np.float64) * GRID
    return ints.astype(np.int64)


def _enc(ks, v, seed):
    v = float(v) if _is_ckks(ks) else int(v)
    return E.encrypt(ks, jnp.asarray(v), jax.random.PRNGKey(seed))


def _bound(ks, v, side):
    return float(v) + side * GRID / 2 if _is_ckks(ks) else int(v)


def _close(ks, got, want):
    """Decrypt comparison bounded by the profile's precision claim
    (exact on bfv)."""
    if _is_ckks(ks):
        return np.allclose(np.asarray(got), np.asarray(want, np.float64),
                           atol=equality_tolerance(ks.params))
    return (np.asarray(got) == np.asarray(want)).all()


def _range(ks, lo, hi, seed):
    return P.Range("v", _enc(ks, _bound(ks, _vals(ks, lo), -1), seed),
                   _enc(ks, _bound(ks, _vals(ks, hi), +1), seed + 1))


# ---------------------------------------------------------------------------
# pad geometry edge cases (the bugfixes that unblock empty/delta tables)
# ---------------------------------------------------------------------------

def test_next_pow2_edge_cases():
    # the n <= 1 cases are the write path's: an empty table and a
    # 1-row delta run must pad to ONE slot (the naive bit-length form
    # returns 2 for n=0)
    assert next_pow2(0) == 1
    assert next_pow2(1) == 1
    assert next_pow2(2) == 2
    assert next_pow2(3) == 4
    assert next_pow2(4) == 4
    assert next_pow2(5) == 8
    assert next_pow2(1023) == 1024
    assert next_pow2(1024) == 1024
    with pytest.raises((ValueError, TypeError)):
        next_pow2(-1)


def test_pad_rows_pow2_shares_next_pow2_geometry():
    for n in (0, 1, 2, 3, 5):
        padded = pad_rows_pow2(np.arange(n, dtype=np.int64))
        assert padded.shape == (next_pow2(n),)
        assert (padded[:n] == np.arange(n)).all()
        assert (padded[n:] == 0).all()
    # n_target must still be a pow2 >= max(n, 1)
    with pytest.raises(ValueError):
        pad_rows_pow2(np.arange(3, dtype=np.int64), n_target=2)
    with pytest.raises(ValueError):
        pad_rows_pow2(np.arange(2, dtype=np.int64), n_target=3)


def test_empty_table_and_insert_into_empty(scheme_ks):
    ks = scheme_ks
    t = Table.empty(ks, "t", ["v"], jax.random.PRNGKey(1))
    assert t.n_rows == 0 and t.n_padded == 1 and t.n_total == 0
    assert not t.valid.any()
    # a query against a fully-empty table answers (no crash, no rows)
    r = db.execute(ks, t, _range(ks, 0, 100, 10))
    assert len(r.row_ids) == 0
    ids = t.insert(ks, {"v": _vals(ks, [5, 9, 2])}, jax.random.PRNGKey(2))
    assert ids.tolist() == [0, 1, 2]
    got = t.decrypt_column(ks, "v")
    assert _close(ks, got, _vals(ks, [5, 9, 2]))
    r = db.execute(ks, t, _range(ks, 3, 9, 12))
    assert sorted(r.row_ids) == [0, 1]


def test_from_arrays_rejects_zero_padding_underflow():
    with pytest.raises(ValueError):
        pad_rows_pow2(np.arange(4, dtype=np.int64), n_target=1)


# ---------------------------------------------------------------------------
# per-column keys derive from the NAME (crc32), not dict insertion order
# ---------------------------------------------------------------------------

def test_column_keys_are_order_independent(bfv_engine_ks):
    ks = bfv_engine_ks
    key = jax.random.PRNGKey(7)
    a = np.array([1, 2, 3], np.int64)
    b = np.array([9, 8, 7], np.int64)
    t_ab = Table.from_arrays(ks, "t", {"a": a, "b": b}, key)
    t_ba = Table.from_arrays(ks, "t", {"b": b, "a": a}, key)
    for c in ("a", "b"):
        assert (np.asarray(t_ab.columns[c].c0)
                == np.asarray(t_ba.columns[c].c0)).all()
        assert (np.asarray(t_ab.columns[c].c1)
                == np.asarray(t_ba.columns[c].c1)).all()
    # distinct columns still get distinct keys
    assert not (np.asarray(column_key(key, "a"))
                == np.asarray(column_key(key, "b"))).all()


def test_base_and_delta_ingest_agree_on_column_keys(bfv_engine_ks):
    # a delta run ingested under the same key produces the same
    # ciphertext rows a base ingest of those rows would — the compat
    # contract that makes compaction's ciphertext append well-defined
    ks = bfv_engine_ks
    key = jax.random.PRNGKey(11)
    rows = {"a": np.array([4, 6], np.int64), "b": np.array([1, 0], np.int64)}
    base = Table.from_arrays(ks, "d", rows, key)
    t = Table.empty(ks, "d", ["a", "b"], jax.random.PRNGKey(0))
    t.insert(ks, rows, key)
    for c in ("a", "b"):
        assert (np.asarray(base.columns[c].c0)
                == np.asarray(t.delta.columns[c].c0)).all()


# ---------------------------------------------------------------------------
# storage: int32 column blocks, int64 for every reader but the scan
# ---------------------------------------------------------------------------

def _assert_stored_int32(t):
    blocks = list(t.columns.values())
    if t.delta is not None:
        blocks += list(t.delta.columns.values())
    for ct in blocks:
        assert ct.c0.dtype == ct.c1.dtype == jnp.int32


def _int64_bytes(t):
    """What `t`'s blocks would take as int64."""
    total = sum(2 * ct.c0.size * 8 for ct in t.columns.values())
    return total + (0 if t.delta is None else _int64_bytes(t.delta))


def test_blocks_stored_int32_and_read_as_int64(scheme_ks):
    ks = scheme_ks
    base = np.array([12, 3, 40, 7, 25, 18], np.int64)
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, base),
                                    "w": _vals(ks, base + 1)},
                          jax.random.PRNGKey(40))
    _assert_stored_int32(t)
    assert 2 * t.ciphertext_bytes() == _int64_bytes(t)
    # a fresh delta run, then a grown one (`append_rows`), then an update
    t.insert(ks, {"v": _vals(ks, [33, 9]), "w": _vals(ks, [34, 10])},
             jax.random.PRNGKey(41))
    _assert_stored_int32(t)
    t.insert(ks, {"v": _vals(ks, [50, 1, 29]), "w": _vals(ks, [51, 2, 30])},
             jax.random.PRNGKey(42))
    _assert_stored_int32(t)
    t.update(ks, [1], {"v": _vals(ks, [44]), "w": _vals(ks, [45])},
             jax.random.PRNGKey(43))
    _assert_stored_int32(t)
    assert 2 * t.ciphertext_bytes() == _int64_bytes(t)
    allv = np.array([12, 3, 40, 7, 25, 18, 33, 9, 50, 1, 29, 44], np.int64)
    ids = np.arange(t.n_total)

    def check_readers():
        for c, off in (("v", 0), ("w", 1)):
            want = _vals(ks, allv + off)
            got = t.gather(c, ids)
            assert got.c0.dtype == got.c1.dtype == jnp.int64
            assert _close(ks, E.decrypt(ks, got), want)
            col = t.column(c)
            assert col.c0.dtype == col.c1.dtype == jnp.int64
            assert _close(ks, E.decrypt(ks, col)[:t.n_rows],
                          want[:t.n_rows])
            scan = t.scan_column(c)
            assert scan.c0.dtype == scan.c1.dtype == jnp.int64
            slots = t.slot_global_ids
            assert _close(ks, np.asarray(E.decrypt(ks, scan))[slots >= 0],
                          want[slots[slots >= 0]])
            assert _close(ks, t.decrypt_column(ks, c), want)

    def check_queries(seed):
        # ring arithmetic on stored rows: the top-k's compare network
        # and the projection's decrypt answer exactly
        live = t.alive
        q = P.Query(where=_range(ks, 5, 45, seed), top_k=P.TopK("v", 3),
                    select=("v", "w"))
        res = db.execute(ks, t, q)
        match = live & (allv >= 5) & (allv <= 45)
        want = sorted(allv[match].tolist(), reverse=True)[:3]
        assert allv[res.row_ids].tolist() == want
        for c, off in (("v", 0), ("w", 1)):
            assert res.columns[c].c0.dtype == jnp.int64
            assert _close(ks, E.decrypt(ks, res.columns[c]),
                          _vals(ks, np.asarray(want) + off))

    check_readers()
    check_queries(90)
    db.compact(ks, t)
    assert not t.has_delta
    _assert_stored_int32(t)
    assert 2 * t.ciphertext_bytes() == _int64_bytes(t)
    check_readers()
    check_queries(92)


# ---------------------------------------------------------------------------
# union reads: base ∪ delta scans, index probes, tombstones
# ---------------------------------------------------------------------------

def test_insert_then_scan_and_index_agree(scheme_ks, rng):
    ks = scheme_ks
    base = rng.choice(np.arange(2, 60, 2), size=12, replace=False)
    extra = np.array([5, 31, 47])
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, base)},
                          jax.random.PRNGKey(3))
    idx = db.SortedIndex.build(ks, t, "v")
    t.insert(ks, {"v": _vals(ks, extra)}, jax.random.PRNGKey(4))
    allv = np.concatenate([base, extra])
    lo, hi = 10, 48
    want = sorted(np.nonzero((allv >= lo) & (allv <= hi))[0])
    r_scan = db.execute(ks, t, _range(ks, lo, hi, 20))
    r_idx = db.execute(ks, t, _range(ks, lo, hi, 22), indexes={"v": idx})
    assert sorted(r_scan.row_ids) == want
    assert sorted(r_idx.row_ids) == want
    # the union probe costs the base fan-out + one per-run search:
    # <= 2·ceil(log2 n_base) + 2·ceil(log2 n_delta) per lane pair
    n_b, n_d = next_pow2(len(base)), next_pow2(len(extra))
    per_probe = 2 * (max(1, (n_b - 1).bit_length())
                     + max(1, (n_d - 1).bit_length()))
    assert r_idx.stats.index_compares <= 2 * per_probe  # 2 lanes (lo, hi)


def test_duplicate_keys_split_across_base_and_delta(scheme_ks):
    ks = scheme_ks
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, [4, 9, 12])},
                          jax.random.PRNGKey(5))
    idx = db.SortedIndex.build(ks, t, "v")
    t.insert(ks, {"v": _vals(ks, [9, 9])}, jax.random.PRNGKey(6))
    q = P.Eq("v", _enc(ks, _vals(ks, 9), 30),
             eps=EPS_BAND if _is_ckks(ks) else None)
    for indexes in ({}, {"v": idx}):
        r = db.execute(ks, t, q, indexes=indexes)
        assert sorted(r.row_ids) == [1, 3, 4]


def test_delete_all_then_query(scheme_ks):
    ks = scheme_ks
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, [3, 8, 15])},
                          jax.random.PRNGKey(7))
    idx = db.SortedIndex.build(ks, t, "v")
    assert t.delete([0, 1, 2]) == 3
    assert t.delete([1]) == 0          # idempotent tombstones
    assert not t.alive.any() and t.is_mutated
    for indexes in ({}, {"v": idx}):
        r = db.execute(ks, t, _range(ks, 0, 100, 32), indexes=indexes)
        assert len(r.row_ids) == 0
        assert not r.mask.any()
    with pytest.raises(IndexError):
        t.delete([3])


def test_update_is_tombstone_plus_reinsert(scheme_ks):
    ks = scheme_ks
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, [3, 8, 15])},
                          jax.random.PRNGKey(8))
    new_ids = t.update(ks, [1], {"v": _vals(ks, [50])},
                       jax.random.PRNGKey(9))
    assert new_ids.tolist() == [3]
    assert not t.alive[1] and t.alive[3]
    r = db.execute(ks, t, _range(ks, 40, 60, 34))
    assert sorted(r.row_ids) == [3]
    r2 = db.execute(ks, t, _range(ks, 5, 10, 36))
    assert len(r2.row_ids) == 0       # the old version is dead


@pytest.mark.parametrize("use_index", [False, True], ids=["scan", "indexed"])
def test_eps_band_eq_spans_base_and_delta(ckks_keys, use_index):
    # ε-band equality must not care WHERE a row lives: neighbors within
    # the band sit in base and in the delta run
    ks = ckks_keys
    base = np.array([4, 8, 16], np.int64)    # 8·GRID = 2.0 is the target
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, base)},
                          jax.random.PRNGKey(10))
    indexes = {"v": db.SortedIndex.build(ks, t, "v")} if use_index else {}
    t.insert(ks, {"v": _vals(ks, [9, 30])}, jax.random.PRNGKey(11))
    # band ±0.3 around 2.0 captures 8 (=2.0) and 9 (=2.25), not 16 or 30
    q = P.Eq("v", _enc(ks, _vals(ks, 8), 40), eps=EPS_BAND)
    r = db.execute(ks, t, q, indexes=indexes)
    assert sorted(r.row_ids) == [1, 3]


# ---------------------------------------------------------------------------
# compaction: merge network, id stability, no rebuild
# ---------------------------------------------------------------------------

def test_compaction_preserves_answers_and_ids(scheme_ks, rng):
    ks = scheme_ks
    base = rng.choice(np.arange(2, 200, 2), size=30, replace=False)
    extra = np.array([5, 101, 3, 177])
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, base)},
                          jax.random.PRNGKey(12))
    indexes = {"v": db.SortedIndex.build(ks, t, "v")}
    t.insert(ks, {"v": _vals(ks, extra)}, jax.random.PRNGKey(13))
    t.delete([2])
    allv = np.concatenate([base, extra])
    want = sorted(i for i in np.nonzero((allv >= 50) & (allv <= 150))[0]
                  if i != 2)
    before = db.execute(ks, t, _range(ks, 50, 150, 50), indexes=indexes)
    stats = db.compact(ks, t, indexes)
    after = db.execute(ks, t, _range(ks, 50, 150, 52), indexes=indexes)
    assert sorted(before.row_ids) == want
    assert sorted(after.row_ids) == want          # global ids are STABLE
    assert not t.has_delta and t.n_rows == len(allv)
    assert not t.alive[2]                         # tombstones survive
    assert _close(ks, t.decrypt_column(ks, "v"), _vals(ks, allv))
    assert stats.merge_rounds == 1 and stats.indexes_merged == 1
    # the merge is a merge, not a rebuild
    assert 0 < stats.merge_compares < stats.rebuild_compares
    L = next_pow2(max(len(base), len(extra)))
    assert stats.merge_compares <= L * (1 + max(1, L.bit_length() - 1))
    assert stats.rebuild_compares == bitonic_compare_count(len(allv))
    # compacting again is a no-op
    again = db.compact(ks, t, indexes)
    assert again.merge_compares == 0 and again.n_delta == 0


def test_compaction_is_pure_ciphertext_append(bfv_engine_ks):
    # no base row is re-encrypted: the folded base's leading rows are
    # byte-identical to the pre-compaction base ciphertexts
    ks = bfv_engine_ks
    t = Table.from_arrays(ks, "t", {"v": np.array([7, 1, 5], np.int64)},
                          jax.random.PRNGKey(14))
    base_c0 = np.asarray(t.columns["v"].c0)[:3].copy()
    t.insert(ks, {"v": np.array([2, 9], np.int64)}, jax.random.PRNGKey(15))
    delta_c0 = np.asarray(t.delta.columns["v"].c0)[:2].copy()
    db.compact(ks, t)
    folded = np.asarray(t.columns["v"].c0)
    assert (folded[:3] == base_c0).all()
    assert (folded[3:5] == delta_c0).all()


# ---------------------------------------------------------------------------
# shard invariance of the mutated view
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 3, 4])
def test_shard_invariance_of_mutated_view(scheme_ks, shards):
    ks = scheme_ks
    base = np.arange(2, 2 + 2 * 11, 2)
    extra = np.array([5, 17, 3])
    allv = np.concatenate([base, extra])
    spec = db.ShardSpec.create(shards, use_mesh=False)
    st = db.ShardedTable.from_arrays(ks, "s", {"v": _vals(ks, base)},
                                     jax.random.PRNGKey(16), spec=spec)
    indexes = {"v": db.ShardedIndex.build(ks, st, "v")}
    st.insert(ks, {"v": _vals(ks, extra)}, jax.random.PRNGKey(17))
    st.delete([1])
    want = sorted(i for i in np.nonzero((allv >= 4) & (allv <= 18))[0]
                  if i != 1)
    r = db.execute(ks, st, _range(ks, 4, 18, 60), indexes=indexes)
    assert sorted(r.row_ids) == want
    # the decrypted global view is byte-identical to a from-scratch
    # single table over the same rows, for EVERY shard count
    ref = _vals(ks, allv)
    assert _close(ks, st.decrypt_column(ks, "v"), ref)
    stats = db.compact(ks, st, indexes)
    assert not st.has_delta
    assert stats.shards == shards
    assert _close(ks, st.decrypt_column(ks, "v"), ref)
    r2 = db.execute(ks, st, _range(ks, 4, 18, 62), indexes=indexes)
    assert sorted(r2.row_ids) == want
    # inserts after compaction (non-contiguous shard ownership) still
    # route, read, and decrypt in global id order
    st.insert(ks, {"v": _vals(ks, [4])}, jax.random.PRNGKey(18))
    assert _close(ks, st.decrypt_column(ks, "v"),
                  _vals(ks, np.concatenate([allv, [4]])))


# ---------------------------------------------------------------------------
# server mutation queues + compaction under load
# ---------------------------------------------------------------------------

def test_query_server_fifo_mutations(scheme_ks):
    ks = scheme_ks
    base = np.array([10, 3, 7, 14, 1, 8], np.int64)
    t = Table.from_arrays(ks, "t", {"v": _vals(ks, base)},
                          jax.random.PRNGKey(19))
    idx = db.SortedIndex.build(ks, t, "v")
    srv = db.QueryServer(ks, t, indexes={"v": idx}, batch=2)
    q1 = srv.submit(_range(ks, 5, 12, 70))
    mi = srv.submit_insert({"v": _vals(ks, [6, 12])}, jax.random.PRNGKey(20))
    q2 = srv.submit(_range(ks, 5, 12, 72))
    md = srv.submit_delete([0])
    q3 = srv.submit(_range(ks, 5, 12, 74))
    res = srv.run()
    allv = np.concatenate([base, [6, 12]])
    w1 = sorted(np.nonzero((base >= 5) & (base <= 12))[0])
    w2 = sorted(np.nonzero((allv >= 5) & (allv <= 12))[0])
    w3 = [i for i in w2 if i != 0]
    assert sorted(res[q1].row_ids) == w1      # pre-insert snapshot
    assert sorted(res[q2].row_ids) == w2      # sees the insert
    assert sorted(res[q3].row_ids) == w3      # sees the delete too
    assert isinstance(res[mi], db.MutationResult)
    assert res[mi].row_ids.tolist() == [6, 7]
    assert res[md].deleted == 1


def test_sharded_server_compaction_under_load(scheme_ks):
    # the CI compaction-under-load scenario: queries keep answering
    # correctly while threshold-triggered compactions land between
    # batches (queries before the compaction run over base ∪ delta,
    # queries after run over the folded base — same answers)
    ks = scheme_ks
    base = np.arange(1, 17)
    spec = db.ShardSpec.create(4, use_mesh=False)
    st = db.ShardedTable.from_arrays(ks, "s", {"v": _vals(ks, base)},
                                     jax.random.PRNGKey(21), spec=spec)
    indexes = {"v": db.ShardedIndex.build(ks, st, "v")}
    srv = db.ShardedQueryServer(ks, st, indexes=indexes, batch=2,
                                compact_threshold=3)
    live = list(base)
    truth = {}
    rng = np.random.default_rng(23)
    next_val = 100
    for step in range(3):
        lo, hi = sorted(rng.choice(np.arange(1, 120), 2, replace=False))
        qid = srv.submit(_range(ks, int(lo), int(hi), 80 + 4 * step))
        snapshot = np.array(live)
        truth[qid] = int(((snapshot >= lo) & (snapshot <= hi)).sum())
        ins = [next_val, next_val + 1, next_val + 2]
        next_val += 3
        srv.submit_insert({"v": _vals(ks, ins)},
                          jax.random.PRNGKey(30 + step))
        live.extend(ins)
        qid2 = srv.submit(_range(ks, int(lo), int(hi), 82 + 4 * step))
        snapshot = np.array(live)
        truth[qid2] = int(((snapshot >= lo) & (snapshot <= hi)).sum())
    res = srv.run()
    for qid, want in truth.items():
        assert len(res[qid].row_ids) == want, (qid, want)
    # the threshold actually fired, and the folds went through the
    # merge network (compares attributed), never a rebuild pass
    assert len(srv.compaction_log) >= 1
    assert all(c.merge_rounds >= 1 for c in srv.compaction_log)
    assert not st.has_delta


# ---------------------------------------------------------------------------
# joins guard the write path
# ---------------------------------------------------------------------------

def test_join_refuses_pending_delta_but_allows_tombstones(bfv_engine_ks):
    ks = bfv_engine_ks
    left = Table.from_arrays(ks, "l", {"k": np.array([1, 2, 3], np.int64)},
                             jax.random.PRNGKey(24))
    right = Table.from_arrays(ks, "r", {"k": np.array([2, 3, 4], np.int64)},
                              jax.random.PRNGKey(25))
    join = P.Join(left=None, right=None, on=("k", "k"))
    left.insert(ks, {"k": np.array([5], np.int64)}, jax.random.PRNGKey(26))
    with pytest.raises(ValueError, match="compact"):
        db.execute_join(ks, left, right, join)
    db.compact(ks, left)
    right.delete([2])          # tombstones are fine: the row just drops
    res = db.execute_join(ks, left, right, join)
    assert res.pairs.tolist() == [[1, 0], [2, 1]]
