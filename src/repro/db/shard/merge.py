"""Cross-shard encrypted merge networks (sort / top-k over shard blocks).

A sharded `OrderBy`/`TopK` never gathers all rows to one sort: each
shard first resolves its own candidates with a LOCAL bitonic network
(all shards riding the same batched Eval stages — the flattened
`[S·M, ...]` stack tiles block-local compare-exchanges across shards),
then a log₂S-depth cross-shard merge combines the per-shard results:

  * top-k:  per-shard partial bitonic top-k down to one descending
    kp-block per shard, then the max-merge TOURNAMENT continues across
    shard boundaries — merge overhead is (S-1)·(kp + kp/2·log₂kp)
    compares on k-sized blocks, independent of n.
  * sort:   per-shard full bitonic sort, then log₂S pairwise sorted-run
    merges (the half-cleaner + bitonic-merge network: each round is
    L/2·(1+log₂L) compares per pair on runs of length L) — O(n log n·
    log S) merge compares versus the O(n log² n) of re-sorting.

Everything runs on the `core.compare` compare-exchange machinery
(`_compare_swap` / `_bitonic_pairs` / `_block_pairs`), so stage
semantics — including FAE tie coin-flips and id-based (never value-
based) sentinel stripping — are definitionally identical to the
single-device `encrypted_sort` / `encrypted_topk`.

All functions take a FLATTENED `[S·M]` ciphertext whose blocks are the
shards' padded candidate lists plus an `ids` array carrying global row
ids (-1 on sentinel pads); compare counts come back split into the
per-shard phase and the cross-shard merge phase so benchmarks and stats
can attribute them.  Shard counts that are not powers of two are padded
with all-sentinel blocks by the caller (`pad_shard_blocks`).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compare as C
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet


def _obs_stage(site: str, glo) -> None:
    """Launch accounting for one compare-exchange stage (one batched
    Eval over `glo.shape[0]` lanes); no-op unless obs is enabled."""
    if not obs.is_enabled():
        return
    obs.jit_launch(site, (int(glo.shape[0]),))
    obs.count("eval.launches")
    obs.count("eval.lanes", int(glo.shape[0]))


def shard_block_sort(ks: KeySet, cmp: Callable, c0, c1, ids, *,
                     block: int, descending: bool = False) -> Tuple[
                         jax.Array, jax.Array, jax.Array, int]:
    """Sort each contiguous `block`-sized run independently — every stage
    of the tiled bitonic network is ONE batched Eval across all runs."""
    n = c0.shape[0]
    assert n % block == 0
    compares = 0
    with obs.span("merge.block_sort", rows=int(n), block=int(block)):
        for lo, hi, asc in C._bitonic_pairs(block):
            flags = ~asc if descending else asc
            glo, ghi, gasc = C._block_pairs(n // block, block, lo, hi, flags)
            _obs_stage("merge.block_sort", glo)
            c0, c1, ids = C._compare_swap(ks, cmp, c0, c1, ids,
                                          glo, ghi, gasc)
            compares += int(glo.shape[0])
    return c0, c1, ids, compares


def merge_sorted_runs(ks: KeySet, cmp: Callable, c0, c1, ids, *,
                      run: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                         int]:
    """Merge equal-length ascending runs pairwise until ONE ascending run
    remains (log₂(n/run) rounds, every stage one batched Eval).

    Round structure per pair of runs (a, b) of length L: the half-cleaner
    compare-exchanges a[i] against b[L-1-i] (after which max(a') <=
    min(b') and both halves are bitonic), then each half bitonic-merges
    in log₂L strides — L·(1+log₂L) compares per pair-merge.
    """
    n = c0.shape[0]
    assert n % run == 0 and n // run == C.next_pow2(n // run)
    compares = 0
    while run < n:
        with obs.span("merge.round", run=int(run), rows=int(n)):
            pairs = n // (2 * run)
            i = np.arange(run)
            # half-cleaner: a[i] vs b[run-1-i], smaller stays in a
            glo, ghi, gasc = C._block_pairs(pairs, 2 * run,
                                            i, 2 * run - 1 - i,
                                            np.ones(run, bool))
            _obs_stage("merge.round", glo)
            c0, c1, ids = C._compare_swap(ks, cmp, c0, c1, ids,
                                          glo, ghi, gasc)
            compares += int(glo.shape[0])
            stride = run // 2
            while stride >= 1:
                within = np.arange(run)
                p = within[(within & stride) == 0]
                glo, ghi, gasc = C._block_pairs(2 * pairs, run,
                                                p, p + stride,
                                                np.ones(p.shape[0], bool))
                _obs_stage("merge.round", glo)
                c0, c1, ids = C._compare_swap(ks, cmp, c0, c1, ids,
                                              glo, ghi, gasc)
                compares += int(glo.shape[0])
                stride //= 2
            run *= 2
    return c0, c1, ids, compares


def topk_tournament(ks: KeySet, cmp: Callable, c0, c1, ids, *, kp: int,
                    stop_blocks: int = 1) -> Tuple[
                        jax.Array, jax.Array, jax.Array, int]:
    """`encrypted_topk`'s max-merge tournament over descending kp-blocks,
    run until `stop_blocks` blocks survive.

    With stop_blocks=S it realizes the per-shard phase (blocks pair only
    within their shard: shard regions are contiguous with a power-of-two
    block count, and compaction keeps them contiguous); continuing with
    stop_blocks=1 is the cross-shard merge phase.
    """
    n_live = c0.shape[0]
    assert n_live % kp == 0
    compares = 0
    while n_live > stop_blocks * kp:
        with obs.span("merge.topk_round", live=int(n_live), kp=int(kp)):
            blocks = n_live // kp
            j = jnp.arange(blocks // 2)
            i = jnp.arange(kp)
            lo_idx = ((2 * j * kp)[:, None] + i[None, :]).ravel()
            hi_idx = (((2 * j + 1) * kp)[:, None]
                      + (kp - 1 - i)[None, :]).ravel()
            keep_larger = jnp.zeros(lo_idx.shape[0], bool)
            _obs_stage("merge.topk_round", lo_idx)
            c0, c1, ids = C._compare_swap(ks, cmp, c0, c1, ids,
                                          lo_idx, hi_idx, keep_larger)
            compares += int(lo_idx.shape[0])
            c0, c1, ids = c0[lo_idx], c1[lo_idx], ids[lo_idx]
            n_live //= 2
            stride = kp // 2
            while stride >= 1:
                within = jnp.arange(kp)
                p = within[(within & stride) == 0]
                glo, ghi, gasc = C._block_pairs(n_live // kp, kp,
                                                p, p + stride,
                                                jnp.zeros(p.shape[0], bool))
                _obs_stage("merge.topk_round", glo)
                c0, c1, ids = C._compare_swap(ks, cmp, c0, c1, ids,
                                              glo, ghi, gasc)
                compares += int(glo.shape[0])
                stride //= 2
    return c0, c1, ids, compares


# ---------------------------------------------------------------------------
# shard-level entry points
# ---------------------------------------------------------------------------

def pad_shard_blocks(ks: KeySet, per_shard: list, *, block: int,
                     pad_value: int, num_blocks: int,
                     pad_salt: int = 0) -> Tuple[Ciphertext, np.ndarray]:
    """Stack per-shard (Ciphertext, global-id array) candidate lists into
    one flattened `[num_blocks·block]` column.

    Each shard's list pads to `block` rows with encrypted `pad_value`
    sentinels (same public-key sentinel construction as `encrypted_sort`
    padding); missing shards (num_blocks = next_pow2(S) > S) become
    all-sentinel blocks.  Pad slots carry id -1 — stripping is by id,
    never by value, exactly the core networks' tie-robust contract.
    Block s's pads encrypt under key `pad_salt + s`, so a caller padding
    shard s alone (`num_blocks=1, pad_salt=s`) gets the pads it would
    get in the stack.
    """
    from repro.db.table import encrypt_constant, widen
    pad_key = jax.random.PRNGKey(0x5A4D)
    c0s, c1s, ids = [], [], []
    for s in range(num_blocks):
        ct, gids = (per_shard[s] if s < len(per_shard)
                    else (None, np.zeros(0, np.int64)))
        m = int(np.asarray(gids).shape[0])
        assert m <= block
        parts0 = [ct.c0] if m else []
        parts1 = [ct.c1] if m else []
        if m < block:
            pad = widen(encrypt_constant(
                ks, pad_value, block - m,
                jax.random.fold_in(pad_key, pad_salt + s)))
            parts0.append(pad.c0)
            parts1.append(pad.c1)
        c0s.append(jnp.concatenate(parts0) if len(parts0) > 1 else parts0[0])
        c1s.append(jnp.concatenate(parts1) if len(parts1) > 1 else parts1[0])
        ids.append(np.concatenate([np.asarray(gids, np.int64),
                                   np.full(block - m, -1, np.int64)]))
    return (Ciphertext(jnp.concatenate(c0s), jnp.concatenate(c1s)),
            np.concatenate(ids))


def sharded_topk(ks: KeySet, cmp: Callable, ct: Ciphertext,
                 ids: np.ndarray, *, num_blocks: int,
                 k: int) -> Tuple[np.ndarray, int, int]:
    """Global descending top-k over per-shard candidate blocks.

    ct/ids: flattened `[num_blocks·M]` stack from `pad_shard_blocks`
    (M a power-of-two multiple of kp = next_pow2(k)).  Returns
    (top-k global ids — may contain -1 if a sentinel coin-flipped its
    way in, caller re-resolves via the tie-robust sort path —,
    per-shard-phase compares, cross-shard merge compares).
    """
    n = ct.c0.shape[0]
    M = n // num_blocks
    kp = C.next_pow2(k)
    assert M % kp == 0 and M == C.next_pow2(M)
    c0, c1 = ct.c0, ct.c1
    gid = jnp.asarray(ids)
    # per-shard phase: descending kp-block sorts, then tournament down to
    # ONE block per shard — every stage batched across all shards
    c0, c1, gid, n_sort = shard_block_sort(ks, cmp, c0, c1, gid,
                                           block=kp, descending=True)
    c0, c1, gid, n_tour = topk_tournament(ks, cmp, c0, c1, gid, kp=kp,
                                          stop_blocks=num_blocks)
    # cross-shard merge: the same tournament, now pairing across shards
    c0, c1, gid, n_merge = topk_tournament(ks, cmp, c0, c1, gid, kp=kp,
                                           stop_blocks=1)
    return np.asarray(gid[:k]), n_sort + n_tour, n_merge


def sharded_sort(ks: KeySet, cmp: Callable, ct: Ciphertext,
                 ids: np.ndarray, *, num_blocks: int) -> Tuple[
                     np.ndarray, int, int]:
    """Globally ascending row ids via per-shard sorts + log-depth merge.

    ct/ids: flattened `[num_blocks·M]` stack from `pad_shard_blocks`
    with ascending sentinels (+max_operand//2).  Returns (real row ids
    ascending by value — sentinels stripped BY ID —, per-shard-phase
    compares, cross-shard merge compares).
    """
    n = ct.c0.shape[0]
    M = n // num_blocks
    c0, c1 = ct.c0, ct.c1
    gid = jnp.asarray(ids)
    c0, c1, gid, n_sort = shard_block_sort(ks, cmp, c0, c1, gid, block=M)
    c0, c1, gid, n_merge = merge_sorted_runs(ks, cmp, c0, c1, gid, run=M)
    gid = np.asarray(gid)
    return gid[gid >= 0], n_sort, n_merge
