"""repro.db engine: plan IR, fused executor, sorted index, batched serving.

Cross-scheme test matrix: every plan-equivalence test runs over BOTH the
bfv (integer, exact) and ckks (float, ε-tolerant) profiles via the
session-cached `scheme_ks` fixture from conftest.py.  On BFV the engine
must be *exact*; on CKKS the test data lives on a coarse value grid
(GRID) whose spacing dwarfs the profile's equality tolerance, so every
comparison decision is unambiguous and the expected masks are still
exact — approximate arithmetic with deterministic answers.  Range bounds
are placed off-grid (± GRID/2) so inclusivity at a bound is never
decided by noise.  ε-band equality gets its own tests with ε chosen so
band membership also has grid-sized margins.

Dataset slices keep CI time bounded; the full-row runs live in
benchmarks/db_engine.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import db
from repro.core import encrypt as E
from repro.core.ckks import equality_tolerance
from repro.data import DATASETS, load_dataset

GRID = 0.25        # ckks float grid (>> test-ckks equality tolerance ~0.016)
EPS_BAND = 0.3     # ε-band that captures exactly the ±1-grid-step neighbors


def _is_ckks(ks) -> bool:
    return ks.params.profile.scheme == "ckks"


def _vals(ks, ints) -> np.ndarray:
    """Scheme-native column values from an integer lattice."""
    ints = np.asarray(ints)
    if _is_ckks(ks):
        return ints.astype(np.float64) * GRID
    return ints.astype(np.int64)


def _enc(ks, v, seed):
    v = float(v) if _is_ckks(ks) else int(v)
    return E.encrypt(ks, jnp.asarray(v), jax.random.PRNGKey(seed))


def _bound(ks, v, side):
    """Range bound: off-grid under ckks so inclusivity is unambiguous."""
    return float(v) + side * GRID / 2 if _is_ckks(ks) else int(v)


def _dataset_rows(ks, name, n_rows):
    # ckks profiles have t=0 (no plaintext modulus); reduce the integer
    # lattice mod the full 65537 so the float leg sees a realistic spread
    t = ks.params.t or 65537
    vals = load_dataset(name, scheme="bfv", t=t)[:n_rows]
    return _vals(ks, vals)


def _decrypt_close(ks, got, want):
    got = np.asarray(got)
    if _is_ckks(ks):
        # bound decrypt error by the profile's precision claim
        return np.allclose(got, np.asarray(want, np.float64),
                           atol=equality_tolerance(ks.params))
    return got.tolist() == list(want)


# ---------------------------------------------------------------------------
# plan construction / compilation (scheme-independent — one profile)
# ---------------------------------------------------------------------------

def test_plan_compile_structure(bfv_engine_ks):
    ks = bfv_engine_ks
    r = db.Range("v", _enc(ks, 10, 0), _enc(ks, 20, 1))
    e = db.Eq("s", _enc(ks, 5, 2))
    plan = db.compile_plan(db.Query(where=db.And(r, e)))
    assert plan.num_leaves == 2
    assert plan.tree == ("and", [("leaf", 0), ("leaf", 1)])
    # Range lowers to 2 scan atoms, Eq to 1
    assert [a.op for a in plan.scan_atoms(0)] == [">=", "<="]
    assert [a.op for a in plan.scan_atoms(1)] == ["=="]


def test_plan_compile_dedups_repeated_leaves(bfv_engine_ks):
    ks = bfv_engine_ks
    r = db.Range("v", _enc(ks, 10, 0), _enc(ks, 20, 1))
    e1 = db.Eq("s", _enc(ks, 5, 2))
    e2 = db.Eq("s", _enc(ks, 6, 3))
    # r appears twice but compiles to ONE leaf
    plan = db.compile_plan(db.Or(db.And(r, e1), db.And(r, e2)))
    assert plan.num_leaves == 3
    assert plan.tree == ("or", [("and", [("leaf", 0), ("leaf", 1)]),
                                ("and", [("leaf", 0), ("leaf", 2)])])


def test_plan_eps_is_part_of_leaf_identity(bfv_engine_ks):
    ks = bfv_engine_ks
    ct = _enc(ks, 5, 0)
    # same trapdoor, different ε -> different predicates, no dedup
    plan = db.compile_plan(db.Or(db.Eq("v", ct, eps=0.1),
                                 db.Eq("v", ct, eps=0.2)))
    assert plan.num_leaves == 2
    # identical ε (and identical None) still dedups
    plan2 = db.compile_plan(db.Or(db.Eq("v", ct), db.Eq("v", ct)))
    assert plan2.num_leaves == 1
    # ε rides the lowered atoms
    assert plan.scan_atoms(0)[0].eps == 0.1


def test_predicate_operator_sugar(bfv_engine_ks):
    ks = bfv_engine_ks
    r = db.Range("v", _enc(ks, 10, 0), _enc(ks, 20, 1))
    e = db.Eq("v", _enc(ks, 5, 2))
    assert isinstance(r & e, db.And)
    assert isinstance(r | e, db.Or)
    assert isinstance(~r, db.Not)


def test_bare_predicate_compiles_to_query(bfv_engine_ks):
    plan = db.compile_plan(db.Eq("v", _enc(bfv_engine_ks, 5, 0)))
    assert plan.num_leaves == 1 and plan.tree == ("leaf", 0)
    assert plan.query.where is not None


# ---------------------------------------------------------------------------
# table
# ---------------------------------------------------------------------------

def test_table_pads_to_power_of_two_and_roundtrips(scheme_ks):
    ks = scheme_ks
    vals = _vals(ks, np.arange(50))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(0))
    assert t.n_rows == 50 and t.n_padded == 64
    assert t.valid.sum() == 50
    tol = equality_tolerance(ks.params)
    got = t.decrypt_column(ks, "v")
    if _is_ckks(ks):
        np.testing.assert_allclose(got, vals, atol=tol)
    else:
        np.testing.assert_array_equal(got, vals)
    # pad rows are genuine encryptions of 0
    full = t.decrypt_column(ks, "v", include_padding=True)
    assert np.all(np.abs(full[50:]) <= tol)


def test_table_rejects_ragged_columns(bfv_engine_ks):
    with pytest.raises(ValueError):
        db.Table.from_arrays(bfv_engine_ks, "t",
                             {"a": np.arange(4), "b": np.arange(5)},
                             jax.random.PRNGKey(0))


def test_table_rejects_fractional_floats_under_bfv(bfv_engine_ks):
    with pytest.raises(ValueError, match="ckks profile"):
        db.Table.from_arrays(bfv_engine_ks, "t",
                             {"a": np.asarray([1.0, 2.5, 3.0])},
                             jax.random.PRNGKey(0))
    # integral-valued floats are fine (no silent truncation possible)
    t = db.Table.from_arrays(bfv_engine_ks, "t",
                             {"a": np.asarray([1.0, 2.0, 3.0])},
                             jax.random.PRNGKey(0))
    assert t.n_rows == 3


# ---------------------------------------------------------------------------
# executor: fused linear scan (cross-scheme)
# ---------------------------------------------------------------------------

def test_multi_predicate_and_or_matches_plaintext(scheme_ks, rng):
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 200, 60))
    score = _vals(ks, rng.integers(0, 200, 60))
    t = db.Table.from_arrays(ks, "t", {"v": vals, "s": score},
                             jax.random.PRNGKey(1))
    b = lambda v, s: _bound(ks, _vals(ks, np.asarray(v)), s)  # noqa: E731
    q = db.Or(db.And(db.Range("v", _enc(ks, b(40, -1), 0),
                              _enc(ks, b(120, +1), 1)),
                     db.Range("s", _enc(ks, b(0, -1), 2),
                              _enc(ks, b(100, +1), 3))),
              db.Not(db.Range("v", _enc(ks, b(0, -1), 4),
                              _enc(ks, b(150, +1), 5))))
    res = db.execute(ks, t, q)
    lo40, hi120 = _vals(ks, 40), _vals(ks, 120)
    hi100, hi150, lo0 = _vals(ks, 100), _vals(ks, 150), _vals(ks, 0)
    want = (((vals >= lo40) & (vals <= hi120) & (score <= hi100))
            | ~((vals >= lo0) & (vals <= hi150)))
    np.testing.assert_array_equal(res.mask, want)
    # the whole 3-leaf predicate tree ran as ONE fused Eval
    assert res.stats.eval_calls == 1
    assert res.stats.scan_leaves == 3


@pytest.mark.parametrize("name", DATASETS)
def test_end_to_end_query_matches_plaintext(scheme_ks, rng, name):
    """And(Range, Eq) + TopK — plan answers match the plaintext reference
    on a slice of each paper dataset, on both schemes (acceptance: the
    ckks float path agrees within ε; grid data makes 'within ε' exact)."""
    ks = scheme_ks
    vals = _dataset_rows(ks, name, 96)
    aux = _vals(ks, rng.integers(0, 250, len(vals)))
    t = db.Table.from_arrays(ks, name, {"v": vals, "aux": aux},
                             jax.random.PRNGKey(2))
    lo = _bound(ks, np.percentile(vals, 20), -1)
    hi = _bound(ks, np.percentile(vals, 80), +1)
    if not _is_ckks(ks):
        lo, hi = int(lo), int(hi)
    eq_v = aux[0]
    q = db.Query(
        where=db.And(db.Range("v", _enc(ks, lo, 0), _enc(ks, hi, 1)),
                     db.Eq("aux", _enc(ks, eq_v, 2))),
        top_k=db.TopK("v", 3), select=("v",))
    res = db.execute(ks, t, q)
    want_mask = (vals >= lo) & (vals <= hi) & (aux == eq_v)
    np.testing.assert_array_equal(res.mask, want_mask)
    want_top = sorted(vals[want_mask].tolist(), reverse=True)[:3]
    assert vals[res.row_ids].tolist() == want_top
    # projected ciphertexts decrypt to the selected rows
    assert _decrypt_close(ks, E.decrypt(ks, res.columns["v"]), want_top)


def test_indexed_and_linear_plans_agree_with_topk(scheme_ks, rng):
    """And(Range, Eq) + TopK: the indexed and linear execution paths must
    return the same mask and the same top-k value multiset (acceptance
    criterion for the ckks float path; ties may permute row ids)."""
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 400, 72))
    aux = _vals(ks, rng.integers(0, 8, 72))      # duplicate-heavy
    t = db.Table.from_arrays(ks, "t", {"v": vals, "aux": aux},
                             jax.random.PRNGKey(3))
    idx = db.SortedIndex.build(ks, t, "v")
    lo = _bound(ks, np.percentile(vals, 15), -1)
    hi = _bound(ks, np.percentile(vals, 85), +1)
    if not _is_ckks(ks):
        lo, hi = int(lo), int(hi)
    q = db.Query(
        where=db.And(db.Range("v", _enc(ks, lo, 0), _enc(ks, hi, 1)),
                     db.Eq("aux", _enc(ks, aux[3], 2))),
        top_k=db.TopK("v", 4), select=("v",))
    lin = db.execute(ks, t, q)
    ind = db.execute(ks, t, q, indexes={"v": idx})
    want_mask = (vals >= lo) & (vals <= hi) & (aux == aux[3])
    np.testing.assert_array_equal(lin.mask, want_mask)
    np.testing.assert_array_equal(ind.mask, want_mask)
    want_top = sorted(vals[want_mask].tolist(), reverse=True)[:4]
    assert vals[lin.row_ids].tolist() == want_top
    assert vals[ind.row_ids].tolist() == want_top
    # the indexed path resolved Range via binary search, scanned only Eq
    assert ind.stats.indexed_leaves == 1 and ind.stats.scan_leaves == 1


def test_order_by_and_limit(scheme_ks):
    ks = scheme_ks
    vals = _vals(ks, np.asarray([40, 10, 99, 3, 77, 23, 55]))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(4))
    lo, hi = _bound(ks, _vals(ks, 5), -1), _bound(ks, _vals(ks, 90), +1)
    q = db.Query(where=db.Range("v", _enc(ks, lo, 0), _enc(ks, hi, 1)),
                 order_by=db.OrderBy("v", descending=True),
                 limit=db.Limit(3))
    res = db.execute(ks, t, q)
    want = sorted(vals[(vals >= lo) & (vals <= hi)].tolist(),
                  reverse=True)[:3]
    assert vals[res.row_ids].tolist() == want


# ---------------------------------------------------------------------------
# ε-band equality (ckks float semantics)
# ---------------------------------------------------------------------------

def test_eps_band_eq_linear_and_indexed(scheme_ks, rng):
    """Eq(col, v, ε) selects |col - v| <= ε; the linear scan and the
    ε-aware index binary search agree with the plaintext band."""
    ks = scheme_ks
    if not _is_ckks(ks):
        pytest.skip("ε-band equality is a float-column (ckks) feature")
    vals = _vals(ks, rng.integers(0, 60, 48))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(5))
    idx = db.SortedIndex.build(ks, t, "v")
    target = vals[7]
    q = db.Eq("v", _enc(ks, target, 0), eps=EPS_BAND)
    lin = db.execute(ks, t, q)
    ind = db.execute(ks, t, q, indexes={"v": idx})
    want = np.abs(vals - target) <= EPS_BAND
    np.testing.assert_array_equal(lin.mask, want)
    np.testing.assert_array_equal(ind.mask, want)
    assert ind.stats.eval_calls == 0           # resolved entirely via index
    # the band is strictly wider than native equality
    native = db.execute(ks, t, db.Eq("v", _enc(ks, target, 0)))
    assert native.mask.sum() <= lin.mask.sum()
    np.testing.assert_array_equal(native.mask, vals == target)


def test_eps_inclusive_range_bounds(scheme_ks):
    """Range(lo, hi, ε) pulls in rows within ε outside the bounds."""
    ks = scheme_ks
    if not _is_ckks(ks):
        pytest.skip("ε-inclusive bounds are a float-column (ckks) feature")
    vals = np.asarray([0.0, 1.0, 1.2, 1.25, 2.0, 3.0, 3.05, 3.25, 4.0])
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(6))
    lo, hi = 1.25, 3.0
    exact = db.execute(ks, t, db.Range("v", _enc(ks, lo, 0),
                                       _enc(ks, hi, 1)))
    band = db.execute(ks, t, db.Range("v", _enc(ks, lo, 0),
                                      _enc(ks, hi, 1), eps=0.1))
    np.testing.assert_array_equal(exact.mask, (vals >= lo) & (vals <= hi))
    np.testing.assert_array_equal(
        band.mask, (vals >= lo - 0.1) & (vals <= hi + 0.1))


def test_eps_below_noise_floor_clamps_to_native_tau(scheme_ks):
    """An ε under the profile's equality tolerance cannot be resolved —
    it degrades to the native τ (documented contract of eps_to_tau)."""
    ks = scheme_ks
    tol = equality_tolerance(ks.params)
    assert db.eps_to_tau(ks.params, tol / 10) == ks.params.tau
    assert db.eps_to_tau(ks.params, 0.0) == ks.params.tau
    big = db.eps_to_tau(ks.params, tol * 8)
    assert big > ks.params.tau
    with pytest.raises(ValueError):
        db.eps_to_tau(ks.params, -1.0)


# ---------------------------------------------------------------------------
# sorted index (cross-scheme)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", DATASETS)
def test_indexed_equals_linear_range_query(scheme_ks, rng, name):
    ks = scheme_ks
    vals = _dataset_rows(ks, name, 80)
    t = db.Table.from_arrays(ks, name, {"v": vals}, jax.random.PRNGKey(5))
    idx = db.SortedIndex.build(ks, t, "v")
    np.testing.assert_array_equal(vals[idx.perm], np.sort(vals))
    for i in range(3):
        lo, hi = np.sort(rng.choice(vals, 2, replace=False))
        lo, hi = _bound(ks, lo, -1), _bound(ks, hi, +1)
        if not _is_ckks(ks):
            lo, hi = int(lo), int(hi)
        q = db.Range("v", _enc(ks, lo, 10 + i), _enc(ks, hi, 20 + i))
        lin = db.execute(ks, t, q)
        ind = db.execute(ks, t, q, indexes={"v": idx})
        np.testing.assert_array_equal(lin.mask, ind.mask)
        np.testing.assert_array_equal(
            ind.mask, (vals >= lo) & (vals <= hi))
        assert ind.stats.eval_calls == 0          # no linear scan at all
        assert ind.stats.index_compares <= 2 * (int(np.ceil(
            np.log2(len(vals)))) + 1)


def test_index_point_lookup_duplicates(scheme_ks):
    ks = scheme_ks
    vals = _vals(ks, np.asarray([7, 3, 7, 1, 9, 7, 3, 2, 8]))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(7))
    idx = db.SortedIndex.build(ks, t, "v")
    rows = idx.point_lookup(ks, _enc(ks, _vals(ks, 7), 0))
    assert sorted(rows.tolist()) == [0, 2, 5]
    assert idx.point_lookup(ks, _enc(ks, _vals(ks, 4), 1)).size == 0


# ---------------------------------------------------------------------------
# batched multi-query serving (cross-scheme)
# ---------------------------------------------------------------------------

def test_query_server_fuses_batch_into_one_eval(scheme_ks, rng):
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 200, 70))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(8))
    server = db.QueryServer(ks, t, batch=4)
    truth = {}
    for i in range(4):
        a, b = sorted(rng.integers(0, 200, 2).tolist())
        lo = _bound(ks, _vals(ks, a), -1)
        hi = _bound(ks, _vals(ks, b), +1)
        qid = server.submit(db.Range("v", _enc(ks, lo, 100 + i),
                                     _enc(ks, hi, 200 + i)))
        truth[qid] = (vals >= lo) & (vals <= hi)
    results = server.run()
    assert len(server.batch_log) == 1
    # 4 queries, 8 atoms — ONE fused Eval for the whole batch
    assert server.batch_log[0].eval_calls == 1
    for qid, want in truth.items():
        np.testing.assert_array_equal(results[qid].mask, want)


def test_query_server_indexed_lanes(scheme_ks, rng):
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 200, 64))
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(9))
    idx = db.SortedIndex.build(ks, t, "v")
    server = db.QueryServer(ks, t, indexes={"v": idx}, batch=3)
    truth = {}
    for i in range(3):
        a, b = sorted(rng.integers(0, 200, 2).tolist())
        lo = _bound(ks, _vals(ks, a), -1)
        hi = _bound(ks, _vals(ks, b), +1)
        qid = server.submit(db.Range("v", _enc(ks, lo, 300 + i),
                                     _enc(ks, hi, 400 + i)))
        truth[qid] = (vals >= lo) & (vals <= hi)
    results = server.run()
    assert server.batch_log[0].eval_calls == 0     # all lanes via the index
    assert server.batch_log[0].index_compares > 0
    for qid, want in truth.items():
        np.testing.assert_array_equal(results[qid].mask, want)


def test_query_server_counters_reconcile(scheme_ks, rng):
    """Per-query compare lanes sum exactly to the batch totals, on a
    batch mixing indexed lanes and fused-scan atoms (eval_calls is a
    per-query SHARE of the one launch, deliberately not summable)."""
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 200, 48))
    aux = _vals(ks, rng.integers(0, 200, 48))
    t = db.Table.from_arrays(ks, "t", {"v": vals, "a": aux},
                             jax.random.PRNGKey(21))
    idx = db.SortedIndex.build(ks, t, "v")
    server = db.QueryServer(ks, t, indexes={"v": idx}, batch=3)
    qids = []
    for i in range(2):
        a, b = sorted(rng.integers(0, 200, 2).tolist())
        lo = _bound(ks, _vals(ks, a), -1)
        hi = _bound(ks, _vals(ks, b), +1)
        qids.append(server.submit(db.Range("v", _enc(ks, lo, 700 + i),
                                           _enc(ks, hi, 800 + i))))
    qids.append(server.submit(db.Eq("a", _enc(ks, aux[3], 900))))
    results = server.run()
    b = server.batch_log[-1]
    assert sum(results[q].stats.index_compares
               for q in qids) == b.index_compares
    assert sum(results[q].stats.scan_compares
               for q in qids) == b.scan_compares
    assert b.index_compares > 0 and b.scan_compares > 0


def test_query_server_mixed_columns_and_topk(scheme_ks, rng):
    ks = scheme_ks
    vals = _vals(ks, rng.integers(0, 200, 40))
    score = _vals(ks, rng.integers(0, 200, 40))
    t = db.Table.from_arrays(ks, "t", {"v": vals, "s": score},
                             jax.random.PRNGKey(10))
    idx = db.SortedIndex.build(ks, t, "v")
    server = db.QueryServer(ks, t, indexes={"v": idx}, batch=2)
    lo30, hi170 = _bound(ks, _vals(ks, 30), -1), _bound(ks, _vals(ks, 170), +1)
    lo0, hi120 = _bound(ks, _vals(ks, 0), -1), _bound(ks, _vals(ks, 120), +1)
    q1 = db.Query(where=db.And(db.Range("v", _enc(ks, lo30, 0),
                                        _enc(ks, hi170, 1)),
                               db.Range("s", _enc(ks, lo0, 2),
                                        _enc(ks, hi120, 3))),
                  top_k=db.TopK("s", 4))
    q2 = db.Query(where=db.Eq("v", _enc(ks, vals[5], 4)))
    id1, id2 = server.submit(q1), server.submit(q2)
    results = server.run()
    m1 = (vals >= lo30) & (vals <= hi170) & (score <= hi120)
    np.testing.assert_array_equal(results[id1].mask, m1)
    want_top = sorted(score[m1].tolist(), reverse=True)[:4]
    assert score[results[id1].row_ids].tolist() == want_top
    np.testing.assert_array_equal(results[id2].mask, vals == vals[5])


def test_query_server_eps_band_lanes(scheme_ks, rng):
    """A batch mixing an ε-band Eq lane with an exact Range lane: both
    ride one lane-batched search, each lane under its own τ."""
    ks = scheme_ks
    if not _is_ckks(ks):
        pytest.skip("ε-band lanes are a float-column (ckks) feature")
    vals = _vals(ks, rng.integers(0, 50, 56))    # duplicate-heavy grid
    t = db.Table.from_arrays(ks, "t", {"v": vals}, jax.random.PRNGKey(11))
    idx = db.SortedIndex.build(ks, t, "v")
    server = db.QueryServer(ks, t, indexes={"v": idx}, batch=2)
    target = vals[9]
    lo = _bound(ks, np.percentile(vals, 30), -1)
    hi = _bound(ks, np.percentile(vals, 70), +1)
    id1 = server.submit(db.Eq("v", _enc(ks, target, 0), eps=EPS_BAND))
    id2 = server.submit(db.Range("v", _enc(ks, lo, 1), _enc(ks, hi, 2)))
    results = server.run()
    assert server.batch_log[0].eval_calls == 0     # all lanes via the index
    np.testing.assert_array_equal(results[id1].mask,
                                  np.abs(vals - target) <= EPS_BAND)
    np.testing.assert_array_equal(results[id2].mask,
                                  (vals >= lo) & (vals <= hi))


def test_chunked_ingest_decrypts_and_matches_one_shot(bfv_engine_ks,
                                                      monkeypatch):
    """`encrypt_rows` over several chunks writes every row in place:
    decrypts to the plaintext, and a column within one chunk is the
    plain one-call encryption."""
    from repro.db import table as T
    ks = bfv_engine_ks
    m = jnp.arange(16, dtype=jnp.int64) * 3 - 7
    key = jax.random.PRNGKey(5)
    monkeypatch.setattr(T, "INGEST_CHUNK_ROWS", 4)
    ct = T.encrypt_rows(ks, m, key)
    assert ct.c0.shape == (16, ks.params.num_towers, ks.params.n)
    np.testing.assert_array_equal(np.asarray(E.decrypt(ks, ct)),
                                  np.asarray(m))
    with pytest.raises(ValueError):
        T.encrypt_rows(ks, m[:14], key)
    monkeypatch.setattr(T, "INGEST_CHUNK_ROWS", 16)
    one = T.encrypt_rows(ks, m, key)
    ref = E.encrypt(ks, m, key)
    assert jnp.array_equal(one.c0, ref.c0) and jnp.array_equal(one.c1,
                                                               ref.c1)
