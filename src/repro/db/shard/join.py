"""Cross-shard encrypted joins: the [S_l, S_r] shard-pair grid.

Both single-table strategies lift onto sharded layouts without new
comparison machinery:

  * NESTED-LOOP.  The uniform power-of-two block layout means every
    (left shard, right shard) pair is a static [N_l, N_r] sub-grid, so
    the whole join is ONE `[S_l, S_r, N_l, N_r]` broadcast raw-eval
    launch.  On a usable shard mesh it runs under `shard_map`
    (`kernels.ops.shard_eval_values` — the left shard dim places on the
    mesh, the right table broadcasts to every device; HADES eval stays
    row-local, so no collectives); otherwise the same grid evaluates as
    tiled launches on one device.  Decode thresholds apply host-side
    per the join's τ/ε — byte-identical to the unsharded grid because
    `from_table`-sharded tables carry the SAME ciphertext rows.

  * SORT-MERGE.  Each side contributes its per-shard ascending runs
    (reused from a `ShardedIndex`, or built in one batched per-shard
    network).  All S_l + S_r runs pad to one common block and the
    log-depth cross-shard merge network (`merge.merge_sorted_runs`)
    combines them into a single run — the same network that powers
    sharded OrderBy — then the shared adjacency/class/verify back half
    (`db.join.merge_runs_to_pairs`) emits pairs.  Total compares stay
    O((n_l+n_r)·log(n_l+n_r)·log S) versus the full product.

Invariance contract: `JoinResult.pairs` is byte-identical to the
unsharded plan for every (S_l, S_r) — asserted for S ∈ {1, 2, 3, 4} in
tests/test_db_join.py.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet
from repro.db import executor as X
from repro.db import join as J
from repro.db import plan as P
from repro.db.shard import executor as SX
from repro.db.shard.index import ShardedIndex
from repro.db.shard.table import ShardedTable


def _as_sharded(ks: KeySet, table) -> ShardedTable:
    """Normalize a join side to a ShardedTable.  Plain `Table`s wrap as
    one meshless shard via `from_table`, which REUSES the ciphertext
    rows — so mixed Table×ShardedTable joins stay byte-identical to
    their unsharded reference."""
    if isinstance(table, ShardedTable):
        return table
    from repro.db.shard.spec import ShardSpec
    return ShardedTable.from_table(ks, table,
                                   spec=ShardSpec.create(1, use_mesh=False))


def sharded_pair_eval(ks: KeySet, left: ShardedTable, right: ShardedTable,
                      lcol: str, rcol: str, *, engine: str = "jnp",
                      block_pairs: Optional[int] = None,
                      stats: Optional[J.JoinStats] = None) -> np.ndarray:
    """RAW eval values over the full shard-pair grid:
    [S_l, S_r, N_l, N_r] int64.

    On a usable mesh the grid runs under `shard_map`: the left stack
    reshapes to [S_l, 1, N_l, 1, K, n] (shard dim on the mesh axis) and
    the right stack replicates as [S_r, 1, N_r, K, n], broadcasting to
    each device's [S_r, N_l, N_r] slab.  The right rows tile into
    power-of-two chunks so each device's slab stays within
    `block_pairs` eval lanes — the same memory cap the single-table
    tiles enforce, now per shard (`block_pairs=None` resolves through
    the shared lane-budget policy, see `db.join.DEFAULT_BLOCK_PAIRS`).
    Meshless, the grid flattens to a [S_l·N_l, S_r·N_r] pair matrix and
    reuses the tiled single-table launches.  Either way, thresholds are
    NOT applied here (the `fused_eval` raw-value contract)."""
    block_pairs = J._resolve_block_pairs(ks, block_pairs)
    lct, rct = left.columns[lcol], right.columns[rcol]
    S_l, N_l = lct.c0.shape[:2]
    S_r, N_r = rct.c0.shape[:2]
    spec = left.spec
    if spec.shard_map_ok:
        from repro.kernels import ops as KO
        a = Ciphertext(lct.c0[:, None, :, None], lct.c1[:, None, :, None])
        t_r = J._grid_tile(block_pairs, N_r, S_r * N_l)   # pow2, divides N_r
        chunks = []
        for lo in range(0, N_r, t_r):
            b = Ciphertext(rct.c0[:, None, lo:lo + t_r],
                           rct.c1[:, None, lo:lo + t_r])
            chunks.append(np.asarray(KO.shard_eval_values(
                ks, a, b, mesh=spec.mesh, axis_name=spec.axis,
                use_kernel=X._use_kernel(engine))))
            if stats is not None:
                stats.eval_calls += 1
        if stats is not None:
            stats.pair_compares += S_l * S_r * N_l * N_r
        return np.concatenate(chunks, axis=3)
    flat = lambda ct: Ciphertext(  # noqa: E731
        ct.c0.reshape((-1,) + ct.c0.shape[2:]),
        ct.c1.reshape((-1,) + ct.c1.shape[2:]))
    vals = J.pair_eval_values(ks, flat(lct), flat(rct), engine=engine,
                              block_pairs=block_pairs, stats=stats)
    return vals.reshape(S_l, N_l, S_r, N_r).transpose(0, 2, 1, 3)


def _shard_masks(stable: ShardedTable, gmask: np.ndarray) -> List[np.ndarray]:
    """Global [n_rows] row mask -> per-shard [N_sp] padded masks (pad
    slots False).  Reads the slot->id map, so compacted tables — whose
    shard ownership is no longer contiguous in id space — slice
    correctly."""
    out = []
    for s in range(stable.num_shards):
        m = np.zeros(stable.n_padded_per_shard, bool)
        gids = stable.global_ids(s)
        sel = gids >= 0
        m[sel] = gmask[gids[sel]]
        out.append(m)
    return out


def pairs_from_shard_grid(vals: np.ndarray, tau: int, left: ShardedTable,
                          right: ShardedTable, left_mask: np.ndarray,
                          right_mask: np.ndarray) -> np.ndarray:
    """Raw [S_l, S_r, N_l, N_r] grid -> [P, 2] GLOBAL matched row ids in
    canonical lexicographic order (strategy/placement independent)."""
    lmasks = _shard_masks(left, left_mask)
    rmasks = _shard_masks(right, right_mask)
    chunks = []
    for sl in range(left.num_shards):
        for sr in range(right.num_shards):
            sub = np.abs(vals[sl, sr]) < tau
            sub &= lmasks[sl][:, None] & rmasks[sr][None, :]
            idx = np.argwhere(sub)
            if idx.size:
                idx[:, 0] = left.global_ids(sl)[idx[:, 0]]
                idx[:, 1] = right.global_ids(sr)[idx[:, 1]]
                chunks.append(idx)
    if not chunks:
        return np.zeros((0, 2), dtype=np.int64)
    pairs = np.concatenate(chunks)
    return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def _side_mask_sharded(ks: KeySet, stable: ShardedTable,
                       plan: Optional[P.CompiledPlan], *,
                       indexes: Optional[Dict[str, ShardedIndex]],
                       engine: str,
                       stats: SX.ShardedExecStats) -> np.ndarray:
    """One join side -> its GLOBAL [n_rows] row mask, through the sharded
    filter / merge-order machinery (mirrors `db.join._side_mask`,
    including its contract for mutated sides: a pending delta run is
    refused — compact first — while tombstoned rows just drop out of
    the mask)."""
    if stable.has_delta:
        raise ValueError(
            f"sharded table {stable.name!r} has {stable.n_delta} "
            "uncompacted delta rows — joins address base slots; run "
            "repro.db.delta.compact first")
    if plan is None:
        return stable.alive.copy()
    leaf_masks = SX.sharded_filter_masks(ks, stable, plan, indexes=indexes,
                                         engine=engine, stats=stats)
    mask = SX.combine_shard_masks(stable, plan, leaf_masks)
    q = plan.query
    if q.top_k is not None or q.order_by is not None or q.limit is not None:
        row_ids = SX.order_rows_sharded(ks, stable, q, np.nonzero(mask)[0],
                                        stats)
        mask = np.zeros(stable.n_rows, bool)
        mask[row_ids] = True
    return mask


def _shard_runs(ks: KeySet, stable: ShardedTable, column: str,
                index: Optional[ShardedIndex], id_base: int,
                stats: J.JoinStats) -> List[Tuple[Ciphertext, np.ndarray]]:
    """One side's per-shard ascending runs with GLOBAL combined-key ids
    (shard-local perm + shard offset + the side's `id_base`).  Reuses the
    side's ShardedIndex, building one (cost attributed) when absent."""
    if index is None:
        index = ShardedIndex.build(ks, stable, column)
        stats.build_compares += index.build_compares
    runs = []
    for s, ix in enumerate(index.shards):
        ct, perm = ix.sorted_run()
        # per-shard perms are LOCAL slots; the slot->id map lifts them to
        # global ids (contiguous-offset arithmetic breaks after compaction)
        runs.append((ct, id_base + stable.global_ids(s)[perm]))
    return runs


def execute_join_sharded(ks: KeySet, left, right, join: P.Join, *,
                         strategy: str = "auto",
                         left_indexes: Optional[Dict[str, object]] = None,
                         right_indexes: Optional[Dict[str, object]] = None,
                         engine: str = "jnp",
                         block_pairs: Optional[int] = None,
                         ) -> J.JoinResult:
    """Run a `Join` where either side is a `ShardedTable`.

    Same result contract as `db.join.execute_join` (which dispatches
    here automatically): canonical `pairs`, per-side masks, projected
    ciphertexts — byte-identical to the unsharded plan for every shard
    count when the sharded tables share ciphertext rows with the
    reference (`from_table`).
    """
    left = _as_sharded(ks, left)
    right = _as_sharded(ks, right)
    cj = P.compile_join(join)
    lcol, rcol = cj.on_columns
    left_indexes = dict(left_indexes or {})
    right_indexes = dict(right_indexes or {})
    stats = J.JoinStats(shards=(left.num_shards, right.num_shards))
    stats.left = SX.ShardedExecStats(shards=left.num_shards,
                                     mesh_devices=left.spec.mesh_devices)
    stats.right = SX.ShardedExecStats(shards=right.num_shards,
                                      mesh_devices=right.spec.mesh_devices)
    stats.strategy = J.resolve_strategy(strategy, lcol in left_indexes,
                                        rcol in right_indexes)
    lmask = _side_mask_sharded(ks, left, cj.left_plan, indexes=left_indexes,
                               engine=engine, stats=stats.left)
    rmask = _side_mask_sharded(ks, right, cj.right_plan,
                               indexes=right_indexes, engine=engine,
                               stats=stats.right)
    tau = J.join_tau(ks, join)
    if stats.strategy == "nested":
        vals = sharded_pair_eval(ks, left, right, lcol, rcol, engine=engine,
                                 block_pairs=block_pairs, stats=stats)
        pairs = pairs_from_shard_grid(vals, tau, left, right, lmask, rmask)
    else:
        n_left = left.n_rows
        runs = (_shard_runs(ks, left, lcol, left_indexes.get(lcol), 0, stats)
                + _shard_runs(ks, right, rcol, right_indexes.get(rcol),
                              n_left, stats))
        pairs = J.merge_runs_to_pairs(
            ks, runs, n_left, tau, verify=J.needs_verify(ks, join),
            gather_left=lambda rows: left.gather_global(lcol, rows),
            gather_right=lambda rows: right.gather_global(rcol, rows),
            left_mask=lmask, right_mask=rmask, stats=stats)
    columns = J._project(cj, left.gather_global, right.gather_global, pairs)
    return J.JoinResult(pairs=pairs, left_mask=lmask, right_mask=rmask,
                        columns=columns, stats=stats)
