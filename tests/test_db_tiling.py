"""Lane-tiled, deduped fused scans: byte-identity + accounting.

The bandwidth fix (column dedup + lane-budgeted tiles) must be
invisible to every consumer of `fused_eval`: the raw eval values are
exact integer ring arithmetic, so tiling the row axis and gathering
deduped columns inside the program must reproduce the untiled launch
BYTE FOR BYTE — across schemes (bfv + ckks), engines (jnp + kernel),
tile sizes (including a ragged tail when the union scan width is not a
multiple of the pow2 tile), delta-widened scans (base ∪ delta), and
the S ∈ {1..4} shard placements.

The accounting side is load-bearing too: `bytes.moved` must reflect the
DEDUPED stack (U unique columns, not A atom copies), `eval.lanes` must
still sum to exactly `scan_compares` across tiles, and `eval.tiles`
must count the launches the budget implies.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import db, obs
from repro.core import encrypt as E
from repro.db import executor as X
from repro.db import plan as P
from repro.db.table import concat_ct_rows, widen
from repro.kernels import ops as KO

GRID = 0.25          # ckks value lattice (>> test-ckks tolerance ~0.016)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """`obs.tracing(fresh=True)` clears state on ENTRY, not exit — drop
    this file's spans/counters so later files see a pristine tracer
    (test_obs asserts the disabled-state buffers are empty)."""
    yield
    obs.TRACER.clear()
    obs.REGISTRY.reset()

BASE_INTS = np.array([5, 1, 9, 3, 7, 2, 8, 4, 6, 0, 11, 13], np.int64)
DELTA_INTS = np.array([10, 3, 12], np.int64)


def _vals(ks, ints):
    if ks.params.profile.scheme == "ckks":
        return np.asarray(ints, np.float64) * GRID
    return np.asarray(ints, np.int64)


def _bound(ks, v):
    if ks.params.profile.scheme == "ckks":
        return float(v) * GRID
    return int(v)


def _enc(ks, v, seed):
    return E.encrypt(ks, jnp.asarray(v), jax.random.PRNGKey(seed))


def _table(ks, ints, name="tiling"):
    return db.Table.from_arrays(ks, name, {"v": _vals(ks, ints)},
                                jax.random.PRNGKey(2))


def _range_query(ks, lo, hi, seed=100):
    return db.Range("v", _enc(ks, _bound(ks, lo), seed),
                    _enc(ks, _bound(ks, hi), seed + 1))


def _scan_atoms(query):
    plan = P.compile_plan(query)
    atoms = []
    for i in range(plan.num_leaves):
        atoms.extend(plan.scan_atoms(i))
    return atoms


# ---------------------------------------------------------------------------
# the lane-budget policy itself
# ---------------------------------------------------------------------------

def test_lane_tile_formula():
    # largest pow2 T with T·lanes_per_row <= budget, clamped to [1, n]
    assert KO.lane_tile(64, 4, 32) == 8
    assert KO.lane_tile(64, 4, 33) == 8          # rounds DOWN to pow2
    assert KO.lane_tile(64, 4, 63) == 8
    assert KO.lane_tile(64, 4, 64) == 16
    assert KO.lane_tile(8, 4, 1 << 20) == 8      # clamped to n_rows
    assert KO.lane_tile(64, 1000, 4) == 1        # never below one row
    # matches the join grid's historical formula exactly
    from repro.db.join import _grid_tile
    for budget in (1 << 10, 1 << 14, 12345):
        for n_l, n_r in ((64, 32), (128, 100), (16, 1 << 12)):
            assert KO.lane_tile(n_l, n_r, budget) == \
                _grid_tile(budget, n_l, n_r)


def test_lane_budget_resolution_order(monkeypatch):
    monkeypatch.delenv("REPRO_LANE_BUDGET", raising=False)
    assert KO.resolve_lane_budget() == KO.DEFAULT_LANE_BUDGET
    assert KO.resolve_lane_budget(default=123) == 123
    monkeypatch.setenv("REPRO_LANE_BUDGET", "4096")
    assert KO.resolve_lane_budget() == 4096      # env beats default
    prev = KO.set_lane_budget(512)
    try:
        assert KO.resolve_lane_budget() == 512   # override beats env
        assert KO.resolve_lane_budget(64) == 64  # explicit beats all
    finally:
        KO.set_lane_budget(prev)
    assert KO.resolve_lane_budget() == 4096


# ---------------------------------------------------------------------------
# byte-identity: tiled/deduped vs the one-shot launch
# ---------------------------------------------------------------------------

def test_fused_eval_tiled_identical_across_schemes(scheme_ks):
    ks = scheme_ks
    table = _table(ks, BASE_INTS)
    # And(Range, Range) on ONE column: 4 atoms, U=1 — the dedup shape
    q = db.And(_range_query(ks, 3, 8, 100), _range_query(ks, 2, 11, 200))
    atoms = _scan_atoms(q)
    assert len(atoms) == 4
    ref = X.fused_eval(ks, table, atoms, lane_budget=1 << 20)  # one tile
    for budget in (4, 16, 31):     # T = 1, 4, and a non-pow2 budget
        out = X.fused_eval(ks, table, atoms, lane_budget=budget)
        np.testing.assert_array_equal(out, ref)
    # and the decoded masks agree with plaintext
    vals = _vals(ks, BASE_INTS)
    want = ((vals >= _bound(ks, 3)) & (vals <= _bound(ks, 8))
            & (vals >= _bound(ks, 2)) & (vals <= _bound(ks, 11)))
    res = db.execute(ks, table, q, lane_budget=16)
    np.testing.assert_array_equal(res.mask, want)


def _held_as_int64(table):
    """The same ciphertexts held as int64 blocks (the scan's input before
    column blocks were stored as int32 residues)."""
    wide = copy.copy(table)
    wide.columns = {c: widen(ct) for c, ct in table.columns.items()}
    if table.delta is not None:
        wide.delta = _held_as_int64(table.delta)
    return wide


def _check_int32_blocks_identical_to_int64(ks, with_delta):
    table = _table(ks, BASE_INTS, name=f"int32_{with_delta}")
    if with_delta:
        table.insert(ks, {"v": _vals(ks, DELTA_INTS)}, jax.random.PRNGKey(9))
        assert table.scan_width == 20      # 16-pad base + 4-pad delta
    wide = _held_as_int64(table)
    for blk, wblk in zip(table.scan_parts("v"), wide.scan_parts("v")):
        assert blk.c0.dtype == blk.c1.dtype == jnp.int32
        assert wblk.c0.dtype == wblk.c1.dtype == jnp.int64
    q = db.And(_range_query(ks, 3, 8, 100), _range_query(ks, 2, 11, 200))
    atoms = _scan_atoms(q)                 # A=4
    # one tile; T=4; T=8, whose tiles leave the 4-row delta block ragged
    for budget in (1 << 20, 16, 32):
        np.testing.assert_array_equal(
            X.fused_eval(ks, table, atoms, lane_budget=budget),
            X.fused_eval(ks, wide, atoms, lane_budget=budget))


@pytest.mark.parametrize("with_delta", [False, True],
                         ids=["base", "base_delta"])
def test_fused_eval_int32_blocks_identical_to_int64(scheme_ks, with_delta):
    _check_int32_blocks_identical_to_int64(scheme_ks, with_delta)


def test_fused_eval_int32_blocks_identical_to_int64_paper_mode(paper_keys):
    # paper mode factors the eval per unique column: the stored tile
    # meets `scale` and the key before any int64 operand could widen it
    _check_int32_blocks_identical_to_int64(paper_keys, with_delta=True)


def test_fused_eval_kernel_engine_tiled_identical(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, BASE_INTS)
    atoms = _scan_atoms(_range_query(ks, 3, 8))
    ref = X.fused_eval(ks, table, atoms, engine="jnp")
    for budget in (8, 1 << 20):
        out = X.fused_eval(ks, table, atoms, engine="kernel",
                           lane_budget=budget)
        np.testing.assert_array_equal(out, ref)


def test_ragged_tail_tile_on_delta_widened_scan(scheme_ks):
    ks = scheme_ks
    table = _table(ks, BASE_INTS, name="tiling_delta")
    table.insert(ks, {"v": _vals(ks, DELTA_INTS)}, jax.random.PRNGKey(9))
    assert table.scan_width == 20          # 16-pad base + 4-pad delta
    q = _range_query(ks, 3, 10)
    atoms = _scan_atoms(q)                 # A=2
    ref = X.fused_eval(ks, table, atoms, lane_budget=1 << 20)
    with obs.tracing():
        out = X.fused_eval(ks, table, atoms, lane_budget=16)  # T=8: 8+8+4
        assert obs.REGISTRY.value("eval.tiles") == 3
        assert obs.REGISTRY.value("eval.launches") == 3
        assert obs.REGISTRY.value("eval.lanes") == 2 * 20
    np.testing.assert_array_equal(out, ref)
    # end-to-end over base ∪ delta, tiled, matches plaintext
    allv = np.concatenate([_vals(ks, BASE_INTS), _vals(ks, DELTA_INTS)])
    want = (allv >= _bound(ks, 3)) & (allv <= _bound(ks, 10))
    res = db.execute(ks, table, q, lane_budget=16)
    np.testing.assert_array_equal(res.mask, want)


@pytest.mark.parametrize("shards", [1, 2, 3, 4])
def test_shard_invariance_with_nondefault_budget(bfv_engine_ks, shards):
    ks = bfv_engine_ks
    table = _table(ks, np.arange(40) % 17, name="tiling_shard")
    q = db.And(_range_query(ks, 3, 11, 300), _range_query(ks, 5, 16, 400))
    ref = db.execute(ks, table, q)
    st = db.ShardedTable.from_table(ks, table,
                                    spec=db.ShardSpec.create(shards))
    for budget in (None, 16):
        res = db.execute(ks, st, q, lane_budget=budget)
        np.testing.assert_array_equal(res.mask, ref.mask)
        np.testing.assert_array_equal(res.row_ids, ref.row_ids)


# ---------------------------------------------------------------------------
# accounting: deduped bytes, tiled launches, reconciled lanes
# ---------------------------------------------------------------------------

def test_dedup_bytes_and_lane_accounting(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, BASE_INTS)
    q = db.And(_range_query(ks, 3, 8, 100), _range_query(ks, 2, 11, 200))
    atoms = _scan_atoms(q)                 # A=4 atoms, U=1 unique column
    W = table.scan_width
    # the stack as stored (int32 blocks), which is what the scan reads
    uniq, sel = X.dedup_atom_columns(
        table, atoms, lambda c: concat_ct_rows(*table.scan_parts(c)))
    assert uniq.c0.shape[0] == 1 and sel.tolist() == [0, 0, 0, 0]
    assert uniq.c0.dtype == jnp.int32
    bounds = X.stack_atom_bounds(atoms)
    with obs.tracing():
        vals = X.fused_eval(ks, table, atoms)
        # bytes moved are the UNIQUE stored stack + bounds (c0 and c1),
        # not A full column copies — the dedup invariant in numbers
        assert obs.REGISTRY.value("bytes.moved") == \
            2 * (uniq.c0.nbytes + bounds.c0.nbytes)
        assert obs.REGISTRY.value("eval.lanes") == len(atoms) * W
        assert obs.REGISTRY.value("eval.launches") == \
            obs.REGISTRY.value("eval.tiles") == 1
    assert vals.shape == (len(atoms), W)


def test_query_server_lane_budget_tiles_and_reconciles(bfv_engine_ks):
    ks = bfv_engine_ks
    table = _table(ks, BASE_INTS)
    vals = _vals(ks, BASE_INTS)
    server = db.QueryServer(ks, table, batch=4, lane_budget=8)
    bounds = [(3, 9), (5, 11), (2, 8)]
    qids = [server.submit(_range_query(ks, lo, hi, 500 + 10 * i))
            for i, (lo, hi) in enumerate(bounds)]
    with obs.tracing():
        res = server.run()
        # 3 queries × 2 atoms = 6 lanes/row, budget 8 -> T=1: 16 tiles,
        # all inside ONE fused_eval pass (eval_calls stays 1)
        names = [s.name for s in obs.TRACER.spans]
        assert names.count("executor.fused_eval") == 1
        n_tiles = names.count("executor.eval_tile")
        assert n_tiles == table.scan_width          # T=1 at budget 8
        assert obs.REGISTRY.value("eval.tiles") == n_tiles
        assert obs.REGISTRY.value("eval.lanes") == \
            server.batch_log[-1].scan_compares
    b = server.batch_log[-1]
    assert b.eval_calls == 1
    assert sum(res[q].stats.scan_compares for q in qids) == b.scan_compares
    for qid, (lo, hi) in zip(qids, bounds):
        want = (vals >= _bound(ks, lo)) & (vals <= _bound(ks, hi))
        np.testing.assert_array_equal(res[qid].mask, want)


def test_join_block_pairs_resolves_through_shared_policy(bfv_engine_ks):
    ks = bfv_engine_ks
    lk = np.arange(16, dtype=np.int64) % 4
    rk = np.arange(8, dtype=np.int64) % 4
    lt = db.Table.from_arrays(ks, "tl", {"k": lk}, jax.random.PRNGKey(30))
    rt = db.Table.from_arrays(ks, "tr", {"k": rk}, jax.random.PRNGKey(31))
    join = db.Join(None, None, on="k")
    want = np.argwhere(lk[:, None] == rk[None, :])
    ref = db.execute_join(ks, lt, rt, join, strategy="nested")
    np.testing.assert_array_equal(ref.pairs, want)
    # a process-wide budget override shrinks the grid tiles (more eval
    # calls), identical pairs — one knob governing scans AND joins
    prev = KO.set_lane_budget(16)       # T = 16 // 8 = 2 left rows/tile
    try:
        res = db.execute_join(ks, lt, rt, join, strategy="nested")
    finally:
        KO.set_lane_budget(prev)
    np.testing.assert_array_equal(res.pairs, want)
    assert res.stats.eval_calls == 8 > ref.stats.eval_calls
