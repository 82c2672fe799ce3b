"""Plan executor: fused batched filtering + encrypted order/top-k stages.

Execution model (one XLA program per stage):

  1. FILTER.  Every scan leaf of the compiled plan contributes 1 (Eq) or
     2 (Range) comparison atoms.  ALL atoms across the whole predicate
     tree are stacked into a single [A, N] batched `eval_value` call —
     a 5-leaf plan over 34k rows is still ONE fused Eval.  The launch
     returns RAW eval values; each atom's decode threshold (the profile
     τ, or the predicate's ε-tolerance via `ckks.eps_to_tau`) is applied
     host-side, so mixed-ε plans share one launch and one jit cache
     entry.  Leaves whose column has a `SortedIndex` skip the scan
     entirely and resolve with O(log n) binary-search compares.
  2. COMBINE.  Atom outcomes -> leaf masks -> boolean tree (host-side
     numpy; the comparison outcomes are exactly what the HADES trapdoor
     reveals to the server).
  3. ORDER / TOPK.  The surviving rows' order column runs through
     `encrypted_sort` / `encrypted_topk` (sentinel padding handles the
     arbitrary match count).
  4. LIMIT + PROJECT.  Slice row ids; gather selected ciphertext columns.

Two-table plans (`plan.Join`) execute in `db/join.py`, which reuses
this module's stage helpers for the per-side filters and adds the
pair-matching strategies (tiled nested-loop grid / sort-merge) on top —
`fused_eval`'s raw-value + host-side-threshold contract is exactly what
lets the join grid share programs across ε's and queries.

Engines: "jnp" evaluates via core/compare, the XLA program every
platform runs; "kernel" routes the fused stage through kernels/ops
(Pallas `cmp_eval`).  The Pallas kernels compute on int64 vectors, which
the TPU compiler refuses, so they run only in interpret mode on the CPU
(tests cross-check them against the jnp path); on a TPU,
engine="kernel" raises the compiler's error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compare as C
from repro.core.ckks import eps_to_tau
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet
from repro.db import plan as P
from repro.db.index import SortedIndex
from repro.db.table import Table, rows_to_mask, widen


@dataclasses.dataclass
class ExecStats:
    """What the engine actually did — benchmarks and tests assert on this."""
    eval_calls: int = 0            # batched Eval launches in the filter stage
    scan_compares: int = 0         # comparisons inside fused linear scans
    index_compares: int = 0        # binary-search probe comparisons (the
    #                                base index AND any delta-run index)
    scan_leaves: int = 0
    indexed_leaves: int = 0
    order_compares: int = 0        # sort / top-k network comparisons
    delta_build_compares: int = 0  # lazy per-delta-run index builds

    @property
    def filter_compares(self) -> int:
        """Total filter-stage compare lanes (fused scans + index probes)."""
        return self.scan_compares + self.index_compares


@dataclasses.dataclass
class QueryResult:
    """One executed plan's answer: matched/ordered row ids, the filter
    mask, still-encrypted projected columns, and the engine stats."""
    row_ids: np.ndarray                      # selected (ordered) row ids
    mask: np.ndarray                         # [n_total] global filter mask
    columns: Dict[str, Ciphertext]           # projected ciphertexts
    stats: ExecStats

    def __len__(self) -> int:
        return int(self.row_ids.shape[0])


def _use_kernel(engine: str) -> bool:
    if engine in ("jnp", "kernel"):
        return engine == "kernel"
    raise ValueError(f"unknown engine {engine!r} (jnp|kernel)")


def _jitted(ks: KeySet, name: str, fn, **jit_kw):
    """Per-KeySet jit cache (stashed on the keyset so lifetimes match).

    Jitting the compare plane matters: the fused XLA program keeps the
    eval's intermediates in registers/cache instead of materializing
    every eager intermediate (measured ~5-15x on scan-sized batches on
    a CPU).
    """
    cache = ks.cache("db_jit")
    if name not in cache:
        cache[name] = jax.jit(fn, **jit_kw)
    return cache[name]


def jitted_eval(ks: KeySet):
    """Jitted raw eval values (no threshold) closed over the keyset —
    the fan-out index search and the join passes decode from this,
    applying their own per-lane τ on the host."""
    return _jitted(ks, "eval", lambda a, b: C.eval_value(ks, a, b))


def jitted_probe(ks: KeySet):
    """Jitted binary-search probe step of a `SortedIndex`: gathers the
    [B] probed rows out of the stored (int32) sorted copy, widens them
    and returns their raw eval values against the B trapdoor lanes —
    one program a step, specialised per (copy rows, lanes).

    The copy is an operand, never closed over (a closure would bake a
    copy-sized constant into the program).  An int32 copy is read in
    place: on a TPU a program that takes an int64 copy splits the whole
    copy into 32-bit planes, whatever rows it reads."""
    def index_probe(c0, c1, probe, b0, b1):
        rows = widen(Ciphertext(c0[probe], c1[probe]))
        return C.eval_value(ks, rows, Ciphertext(b0, b1))
    return _jitted(ks, "index_probe", index_probe)


def jitted_dedup_eval(ks: KeySet, axis: int = 0):
    """Jitted raw eval over a deduped column stack: widens the stack to
    int64 (a stored tile is int32, `db.table.store`; the conversion is
    the program's first operation, so it is tile-sized), gathers the
    unique columns back to per-atom order (`jnp.take` by `sel` on
    `axis`) INSIDE the program, then evaluates against the [A, 1]
    bounds.

    The gather living inside the XLA program is the point — the host
    hands over U unique columns however many atoms alias them, and the
    per-atom copies only ever exist as fused intermediates bounded by
    the tile size, never as a materialized A·N stack.

    In paper mode the dedup goes further: `eval_value` is LINEAR in the
    ciphertext pair (ctΔ then coefficient 0 of `scale·c0 + cek⊛c1` — an
    exact mod-q linear map), so the column-side inner product runs ONCE
    per unique column and the per-atom work collapses to a gather +
    coefficient-0 subtract.  A same-column
    batch of A atoms costs ~1 column transform instead of A — bit-
    identical raw values, this is pure factoring.  Gadget mode keeps
    the joint form: `gadget_keymul` digit-decomposes its operand, which
    is not linear, so splitting it would change the noise."""
    from repro.core import ring as R

    def g0(ct0, ct1):
        # coefficient-0 eval part of one ciphertext: [..., K]
        q, mu = ks.ring.q_arr[:, 0], ks.ring.mu_arr[:, 0]
        scaled = R.mulmod(ct0[..., :, 0], ks.params.scale, q, mu)
        return R.addmod(scaled, C.keyed_coeff0(ks, ct1), q)

    if ks.params.mode == "paper":
        def fn(uc0, uc1, sel, b0, b1):
            uc0, uc1 = widen(Ciphertext(uc0, uc1))
            g_col = jnp.take(g0(uc0, uc1), sel, axis=axis)
            diff = R.submod(g_col, g0(b0, b1), ks.ring.q_arr[:, 0])
            return R.crt_centered(ks.params, diff)
    else:
        def fn(uc0, uc1, sel, b0, b1):
            uc0, uc1 = widen(Ciphertext(uc0, uc1))
            col = Ciphertext(jnp.take(uc0, sel, axis=axis),
                             jnp.take(uc1, sel, axis=axis))
            return C.eval_value(ks, col, Ciphertext(b0, b1))
    return _jitted(ks, f"dedup_eval_ax{axis}", fn)


def scan_tile(x: jax.Array, lo: int, t: int, axis: int = 0) -> jax.Array:
    """Rows [lo, lo + t) of a stored (int32) column block along its row
    `axis`: the one way both executors cut a scan tile.  An eager slice
    (one compiled slice per shape and t; the offset is an operand), so
    the eval program takes only the tile and widens it.  An int32 block
    is read in place: the slice holds no temporary (on a TPU an int64
    block would be split into two 32-bit planes whole, for every
    tile)."""
    return jax.lax.dynamic_slice_in_dim(x, lo, t, axis=axis)


def dedup_atom_columns(table, atoms: List[P.Atom],
                       stack) -> Tuple[Ciphertext, np.ndarray]:
    """Stack each DISTINCT scan column once + the [A] per-atom gather.

    `stack(column)` returns the column's scan ciphertext (`scan_column`
    on a Table, `scan_stack` on a ShardedTable); the returned `sel`
    maps atom i to its row in the unique stack, first-seen order — K
    range atoms over one column contribute ONE stacked copy."""
    order: Dict[str, int] = {}
    for a in atoms:
        order.setdefault(a.column, len(order))
    cols = [stack(c) for c in order]
    axis = 0 if cols[0].c0.ndim == 3 else 1     # after the shard dim
    uniq = Ciphertext(jnp.stack([c.c0 for c in cols], axis=axis),
                      jnp.stack([c.c1 for c in cols], axis=axis))
    sel = np.asarray([order[a.column] for a in atoms], np.int64)
    return uniq, sel


def stack_atom_bounds(atoms: List[P.Atom]) -> Ciphertext:
    """The [A, 1] per-atom trapdoor bounds stack every fused scan
    broadcasts against its column tiles."""
    return Ciphertext(jnp.stack([a.value.c0 for a in atoms])[:, None],
                      jnp.stack([a.value.c1 for a in atoms])[:, None])


def atom_tau(ks: KeySet, atom: P.Atom) -> int:
    """The decode threshold atom resolves to (profile τ or ε-derived)."""
    if atom.eps is None:
        return ks.params.tau
    return eps_to_tau(ks.params, atom.eps)


def jitted_comparator(ks: KeySet):
    """Jitted Alg. 4 trapdoor comparator in `encrypted_sort` signature
    (one object per KeySet, so the sort networks' jitted stages, cached
    per comparator, are shared too)."""
    fae = _jitted(ks, "cmp_fae", lambda a, b: C.compare_fae(ks, a, b))
    cache = ks.cache("db_jit")
    if "cmp_fae_sig" not in cache:
        cache["cmp_fae_sig"] = lambda _ks, a, b: fae(a, b)
    return cache["cmp_fae_sig"]


def fused_eval(ks: KeySet, table: Table, atoms: List[P.Atom], *,
               engine: str = "jnp",
               lane_budget: Optional[int] = None) -> np.ndarray:
    """RAW eval values for all atoms' fused scan: [A, N] int64
    (N = `table.scan_width`: a pending delta run's slots ride the SAME
    program as the base block — base ∪ delta costs one pass, not two).

    Duplicate-free and working-set bounded: each DISTINCT column is read
    ONCE ([U, N] bytes moved, not [A, N] — K range queries over one
    column used to ship K full copies): each tile is cut from the
    stored int32 column blocks (`scan_tile`) and stacked over the U
    columns, and the widening to int64, the per-atom gather and the
    [A, 1] bounds broadcast happen INSIDE the jitted program, so no
    launch copies or converts a column.  The
    base block and a pending delta block tile separately, each into
    power-of-two chunks of T rows with A·T lanes within the lane budget
    (`kernels.ops.lane_tile`; explicit `lane_budget` > `set_lane_budget`
    > `REPRO_LANE_BUDGET` > the device-derived default), so peak
    intermediates stay bounded however many atoms a batch fuses — each
    tile is one launch, same shapes across queries, at most one extra
    ragged-tail shape per block.

    Thresholds are deliberately NOT applied here: each atom decodes its
    own τ (profile default or ε-derived) host-side in `scan_leaf_mask`,
    so a plan mixing exact and ε-band predicates still shares launches.
    """
    from repro.kernels import ops as KO
    with obs.span("executor.fused_eval", atoms=len(atoms),
                  rows=table.scan_width):
        A, W = len(atoms), table.scan_width
        order: Dict[str, int] = {}
        for a in atoms:
            order.setdefault(a.column, len(order))
        sel = np.asarray([order[a.column] for a in atoms], np.int64)
        parts = [table.scan_parts(c) for c in order]   # [U][block]
        bounds = stack_atom_bounds(atoms)
        # bytes read are the deduped reality: U unique stored columns
        # + A bounds, counted once however many tiles launch
        col_bytes = sum(b.c0.nbytes for b in parts[0])
        obs.count("bytes.moved",
                  2 * (len(order) * col_bytes + bounds.c0.nbytes))
        use_kernel = _use_kernel(engine)
        default = KO.device_lane_budget(ks.params)
        sel_j = jnp.asarray(sel)
        out = np.empty((A, W), dtype=np.int64)
        off = 0
        for blk in zip(*parts):
            Wb = blk[0].c0.shape[0]
            T = KO.lane_tile(Wb, A, lane_budget, default=default)
            for lo in range(0, Wb, T):
                t = min(T, Wb - lo)
                with obs.span("executor.eval_tile", offset=off + lo,
                              rows=t):
                    obs.jit_launch("executor.fused_eval",
                                   (len(blk), t) + blk[0].c0.shape[1:],
                                   bounds.c0)
                    obs.count("eval.launches")
                    obs.count("eval.tiles")
                    obs.count("eval.lanes", A * t)
                    c0 = jnp.stack([scan_tile(c.c0, lo, t) for c in blk])
                    c1 = jnp.stack([scan_tile(c.c1, lo, t) for c in blk])
                    if use_kernel:
                        c0, c1 = widen(Ciphertext(c0, c1))
                        col = Ciphertext(jnp.take(c0, sel_j, axis=0),
                                         jnp.take(c1, sel_j, axis=0))
                        vals = KO.broadcast_eval_values(ks, col, bounds)
                    else:
                        vals = jitted_dedup_eval(ks)(
                            c0, c1, sel_j, bounds.c0, bounds.c1)
                    out[:, off + lo:off + lo + t] = np.asarray(vals)
            off += Wb
        return out


def fused_compare(ks: KeySet, table: Table, atoms: List[P.Atom], *,
                  engine: str = "jnp",
                  lane_budget: Optional[int] = None) -> np.ndarray:
    """Three-way outcomes (profile τ) for all atoms' fused scan.

    Compatibility wrapper over `fused_eval` for callers that want the
    -1/0/+1 view; the executor itself consumes the raw values.
    """
    v = fused_eval(ks, table, atoms, engine=engine, lane_budget=lane_budget)
    tau = ks.params.tau
    return np.where(np.abs(v) < tau, 0, np.sign(v)).astype(np.int32)


def _atom_mask(op: str, vals: np.ndarray, tau: int) -> np.ndarray:
    """Raw eval row -> bool mask under this atom's decode threshold.

    vals ≈ scale·Δ_enc·(column - value) + noise, so with the three-way
    decode c = (0 if |vals| < τ else sign):  >= is c >= 0, <= is c <= 0,
    == is c == 0 — written directly on the raw values.
    """
    if op == ">=":
        return vals > -tau
    if op == "<=":
        return vals < tau
    if op == "==":
        return np.abs(vals) < tau
    raise ValueError(f"unknown atom op {op!r}")


def scan_leaf_mask(ks: KeySet, atoms: List[P.Atom], vals: np.ndarray,
                   start: int, count: int) -> np.ndarray:
    """AND the fused-scan raw eval values of one leaf's atoms into its
    row mask, each atom under its own τ (single implementation for
    executor and QueryServer)."""
    a = atoms[start]
    m = _atom_mask(a.op, vals[start], atom_tau(ks, a))
    for j in range(1, count):
        a = atoms[start + j]
        m = m & _atom_mask(a.op, vals[start + j], atom_tau(ks, a))
    return m


def combine_tree(tree: Optional[tuple], leaf_masks: List[np.ndarray],
                 n_padded: int) -> np.ndarray:
    """Fold the compiled boolean tree over per-leaf row masks."""
    if tree is None:
        return np.ones(n_padded, bool)
    kind = tree[0]
    if kind == "leaf":
        return leaf_masks[tree[1]]
    if kind == "and":
        out = np.ones(n_padded, bool)
        for t in tree[1]:
            out &= combine_tree(t, leaf_masks, n_padded)
        return out
    if kind == "or":
        out = np.zeros(n_padded, bool)
        for t in tree[1]:
            out |= combine_tree(t, leaf_masks, n_padded)
        return out
    if kind == "not":
        return ~combine_tree(tree[1], leaf_masks, n_padded)
    raise ValueError(f"bad tree node {tree!r}")


def delta_probe_index(ks: KeySet, table: Table, column: str,
                      stats: ExecStats):
    """The per-delta-run `SortedIndex` for an indexed union probe, with
    lazy-build compares attributed to `stats` exactly once per delta
    state (shared by executor and QueryServer).  None without a delta."""
    if table.n_delta == 0:
        return table.delta_index(ks, column)   # fast path: None, no span
    cached = table._delta_index_cache.get(column)
    fresh = not (cached is not None and cached[0] == table.version)
    with obs.span("delta.index_build", column=column, fresh=fresh):
        didx = table.delta_index(ks, column)
    if didx is not None and fresh:
        stats.delta_build_compares += didx.build_compares
        obs.count("eval.lanes", didx.build_compares)
    return didx


def index_leaf_mask(ks: KeySet, table: Table, idx: SortedIndex,
                    leaf, stats: ExecStats) -> np.ndarray:
    """Resolve one indexed leaf over base ∪ delta as a
    [table.scan_width] slot mask.

    The base `SortedIndex` answers with ~2·log2(n_base) probe compares;
    a pending delta run adds one per-run binary search — at most
    2·ceil(log2 |delta|) extra compares — against its own (lazily built,
    cached) sorted run.  Base row ids ARE base slot ids; delta-local
    hits shift past the base block."""
    before = idx.search_compares
    if isinstance(leaf, P.Range):
        rows = idx.search_range(ks, leaf.lo, leaf.hi, eps=leaf.eps)
    else:
        rows = idx.point_lookup(ks, leaf.value, eps=leaf.eps)
    stats.index_compares += idx.search_compares - before
    slots = [np.asarray(rows, np.int64)]
    didx = delta_probe_index(ks, table, leaf.column, stats)
    if didx is not None:
        before = didx.search_compares
        if isinstance(leaf, P.Range):
            drows = didx.search_range(ks, leaf.lo, leaf.hi, eps=leaf.eps)
        else:
            drows = didx.point_lookup(ks, leaf.value, eps=leaf.eps)
        stats.index_compares += didx.search_compares - before
        slots.append(table.n_padded + np.asarray(drows, np.int64))
    return rows_to_mask(np.concatenate(slots), table.scan_width)


def filter_masks(ks: KeySet, table: Table, plan: P.CompiledPlan, *,
                 indexes: Optional[Dict[str, SortedIndex]] = None,
                 engine: str = "jnp",
                 lane_budget: Optional[int] = None,
                 stats: Optional[ExecStats] = None) -> List[np.ndarray]:
    """Per-leaf row masks over the union slot space (`table.scan_width`):
    indexed leaves via binary search (base index + per-delta-run
    search), the rest via one fused scan covering base AND delta."""
    stats = stats if stats is not None else ExecStats()
    indexes = indexes or {}
    W = table.scan_width
    leaf_masks: List[Optional[np.ndarray]] = [None] * plan.num_leaves
    scan_atoms: List[P.Atom] = []
    scan_slices: List[Tuple[int, int, int]] = []   # (leaf, start, count)
    for i, leaf in enumerate(plan.leaves):
        idx = indexes.get(leaf.column)
        if idx is not None:
            leaf_masks[i] = index_leaf_mask(ks, table, idx, leaf, stats)
            stats.indexed_leaves += 1
        else:
            atoms = plan.scan_atoms(i)
            scan_slices.append((i, len(scan_atoms), len(atoms)))
            scan_atoms.extend(atoms)
            stats.scan_leaves += 1
    if scan_atoms:
        vals = fused_eval(ks, table, scan_atoms, engine=engine,
                          lane_budget=lane_budget)
        stats.eval_calls += 1
        stats.scan_compares += len(scan_atoms) * W
        for leaf_i, start, count in scan_slices:
            leaf_masks[leaf_i] = scan_leaf_mask(ks, scan_atoms, vals,
                                                start, count)
    return leaf_masks  # type: ignore[return-value]


def order_rows(ks: KeySet, table: Table, query: P.Query,
               row_ids: np.ndarray, stats: ExecStats) -> np.ndarray:
    """Apply TopK / OrderBy / Limit to the filtered row ids."""
    n_sel = int(row_ids.shape[0])
    if query.top_k is not None and n_sel:
        k = min(query.top_k.k, n_sel)
        with obs.span("executor.order", kind="topk", rows=n_sel, k=k):
            sub = table.gather(query.top_k.column, row_ids)
            _, sel = C.encrypted_topk(ks, sub, k, jitted_comparator(ks))
        row_ids = row_ids[np.asarray(sel)]
        stats.order_compares += _topk_compares(n_sel, k)
        obs.count("eval.lanes", _topk_compares(n_sel, k))
    elif query.order_by is not None and n_sel:
        with obs.span("executor.order", kind="sort", rows=n_sel):
            sub = table.gather(query.order_by.column, row_ids)
            _, perm = C.encrypted_sort(ks, sub, jitted_comparator(ks))
        row_ids = row_ids[np.asarray(perm)]
        if query.order_by.descending:
            row_ids = row_ids[::-1]
        stats.order_compares += _sort_compares(n_sel)
        obs.count("eval.lanes", _sort_compares(n_sel))
    limit = query.limit_count
    if limit is not None:
        row_ids = row_ids[:limit]
    return row_ids


def _sort_compares(n: int) -> int:
    return C.bitonic_compare_count(n)


def _topk_compares(n: int, k: int) -> int:
    n_pad = C.next_pow2(n)
    kp = C.next_pow2(k)
    if kp >= n_pad:
        return _sort_compares(n_pad)
    total = sum(range(1, kp.bit_length())) * (n_pad // 2)  # block sorts
    live = n_pad
    while live > kp:
        total += live // 2                                  # max-merge
        live //= 2
        total += (kp.bit_length() - 1) * (live // 2)        # re-merge
    return total


def execute(ks: KeySet, table, query, *,
            indexes: Optional[Dict[str, SortedIndex]] = None,
            engine: str = "jnp",
            lane_budget: Optional[int] = None) -> QueryResult:
    """Run a Query (or bare predicate / precompiled plan) against a table.

    Accepts a `Table` or a `ShardedTable` — sharded tables dispatch to
    the shard-parallel executor (`db.shard.execute_sharded`; their
    `indexes` must then be `ShardedIndex` instances), so call sites stay
    placement-agnostic.  `lane_budget` caps the fused scan's per-launch
    eval lanes (None = the shared `kernels.ops` policy default)."""
    import sys
    # sys.modules guard keeps non-shard users import-free: a ShardedTable
    # argument implies repro.db.shard.table is already loaded
    shard_mod = sys.modules.get("repro.db.shard.table")
    if shard_mod is not None and isinstance(table, shard_mod.ShardedTable):
        from repro.db.shard.executor import execute_sharded
        return execute_sharded(ks, table, query, indexes=indexes,
                               engine=engine, lane_budget=lane_budget)
    if isinstance(query, (P.Query, P.Predicate)):
        plan = P.compile_plan(query)
    elif isinstance(query, P.CompiledPlan):
        plan = query
    else:
        raise TypeError(f"cannot execute {query!r}")
    stats = ExecStats()
    with obs.span("executor.execute", leaves=plan.num_leaves):
        leaf_masks = filter_masks(ks, table, plan, indexes=indexes,
                                  engine=engine, lane_budget=lane_budget,
                                  stats=stats)
        slot_mask = combine_tree(plan.tree, leaf_masks, table.scan_width)
        slot_mask &= table.slot_valid      # pads AND tombstones excluded
        row_ids = table.slot_global_ids[np.nonzero(slot_mask)[0]]
        mask = rows_to_mask(row_ids, table.n_total)  # [n_total] global mask
        row_ids = order_rows(ks, table, plan.query, row_ids, stats)
        columns = {c: table.gather(c, row_ids) for c in plan.query.select}
    if obs.is_enabled() and table.n_rows:
        obs.observe("pad.waste", table.n_padded / table.n_rows)
        obs.absorb_exec_stats(stats)
    return QueryResult(row_ids=row_ids, mask=mask,
                       columns=columns, stats=stats)
