"""Public jit'd wrappers around the Pallas kernels, the lane-budget
policy, and the shard_map eval entry.

Handles batch padding (grid blocks need B % block_b == 0) and exposes a
kernel-backed `compare` with the same contract as core.compare, which
tests cross-check against the jnp path.  The kernels run in interpret
mode off the TPU.  On a TPU they are compiled, and the compiler refuses
them: they compute on int64 vectors ("UNIMPLEMENTED: While rewriting
computation to not contain X64 element types, XLA encountered an HLO for
which this rewriting is not implemented: ... tpu_custom_call").  No
served path reaches them there; engine="kernel" raises that error.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import ring as R
from repro.core.compare import ct_sub
from repro.core.encrypt import Ciphertext
from repro.core.gadget import digit_decompose
from repro.core.keys import KeySet
from repro.kernels import cmp_eval as CK
from repro.kernels import ntt as NK


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# lane-budget policy: the one knob bounding every eval launch's working set
# ---------------------------------------------------------------------------

# Ceiling on eval LANES per launch (one lane = one [K, n] polynomial
# compare).  Every eval intermediate scales with the lane count, so
# this is the working-set bound of a launch.  1 << 17 was the fast
# regime of a CPU host's cache (ROADMAP); on a device that reports its
# memory, `device_lane_budget` cuts it to what fits.  Scan tiles
# (`db.executor.fused_eval`, the sharded executor) and join grid tiles
# (`db.join.pair_eval_values`) all resolve through this policy.
DEFAULT_LANE_BUDGET = 1 << 17

# share of device memory one eval launch's temporaries may take: the
# table's columns and indexes live beside it
EVAL_MEMORY_SHARE = 8


def eval_lane_bytes(params) -> int:
    """Upper bound on one eval lane's device temporaries, int64: 4x the
    rows its coefficient-0 inner product reads (gadget: K·D digit rows;
    paper: K tower rows), plus 4 K-tower rows for the lane's gathered
    ciphertext pair and difference.  The TPU compiler's
    `memory_analysis()` of a fused scan tile comes to 0.8x of it at
    paper-bfv (tests/test_tpu_compile.py checks the bound)."""
    rows = params.num_towers
    if params.mode == "gadget":
        rows *= params.gadget_digits_per_tower
    return 8 * params.n * (4 * rows + 4 * params.num_towers)


def device_lane_budget(params) -> int:
    """The default lane budget: `DEFAULT_LANE_BUDGET`, cut so one
    launch's temporaries (`eval_lane_bytes`) fit 1/EVAL_MEMORY_SHARE of
    the device's memory where the backend reports it (a TPU does; the
    CPU backend does not, and keeps the constant).  Outside this share:
    the table itself, stored as int32 (`db.table.store`), whose scan
    tiles (`db.executor.scan_tile`) are cut in place and widened to
    int64 inside the eval program, within the lanes counted here."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return DEFAULT_LANE_BUDGET
    fit = limit // EVAL_MEMORY_SHARE // eval_lane_bytes(params)
    return int(max(1, min(DEFAULT_LANE_BUDGET, fit)))

_LANE_BUDGET_OVERRIDE: int | None = None


def set_lane_budget(budget: int | None) -> int | None:
    """Install a process-wide lane-budget override (None clears it).

    Returns the previous override so callers can restore it — the knob
    every entry point resolves through `resolve_lane_budget`, preferred
    over threading a parameter when tuning a whole serving process.
    """
    global _LANE_BUDGET_OVERRIDE
    prev = _LANE_BUDGET_OVERRIDE
    _LANE_BUDGET_OVERRIDE = None if budget is None else int(budget)
    return prev


def resolve_lane_budget(explicit: int | None = None, *,
                        default: int = DEFAULT_LANE_BUDGET) -> int:
    """The effective lane budget: explicit argument > `set_lane_budget`
    override > `REPRO_LANE_BUDGET` env var > `default` (callers with
    their own historical default — join's `DEFAULT_BLOCK_PAIRS` — pass
    it here so the shared overrides still win)."""
    if explicit is not None:
        return int(explicit)
    if _LANE_BUDGET_OVERRIDE is not None:
        return _LANE_BUDGET_OVERRIDE
    env = os.environ.get("REPRO_LANE_BUDGET")
    if env:
        return int(env)
    return default


def lane_tile(n_rows: int, lanes_per_row: int,
              lane_budget: int | None = None, *,
              default: int = DEFAULT_LANE_BUDGET) -> int:
    """Rows per tile: the largest power of two T with T·lanes_per_row
    within the lane budget, clamped to [1, n_rows].

    The same formula `db.join._grid_tile` has always used for pair
    grids, exposed for every tiled launch: power-of-two tiles keep the
    jit cache warm across queries (at most one extra compiled shape for
    a ragged tail when n_rows is not a multiple of T)."""
    b = resolve_lane_budget(lane_budget, default=default)
    t = max(1, b // max(1, lanes_per_row))
    t = 1 << (t.bit_length() - 1)
    return min(t, n_rows)


def _pad_batch(x: jax.Array, block_b: int):
    b = x.shape[0]
    pad = (-b) % block_b
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, b


def ntt(x: jax.Array, ring: R.Ring, *, block_b: int = NK.DEFAULT_BLOCK_B,
        interpret: bool | None = None) -> jax.Array:
    """Forward negacyclic NTT (br-eval order). x: [B, K, n]."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    xp, b = _pad_batch(x, block_b)
    return NK.ntt_br(xp, ring, fwd=True, block_b=block_b,
                     interpret=interpret)[:b]


def intt(x: jax.Array, ring: R.Ring, *, block_b: int = NK.DEFAULT_BLOCK_B,
         interpret: bool | None = None) -> jax.Array:
    interpret = (not _on_tpu()) if interpret is None else interpret
    xp, b = _pad_batch(x, block_b)
    return NK.ntt_br(xp, ring, fwd=False, block_b=block_b,
                     interpret=interpret)[:b]


def negacyclic_mul(a: jax.Array, b: jax.Array, ring: R.Ring, *,
                   block_b: int = NK.DEFAULT_BLOCK_B,
                   interpret: bool | None = None) -> jax.Array:
    interpret = (not _on_tpu()) if interpret is None else interpret
    ap, nb = _pad_batch(a, block_b)
    bp, _ = _pad_batch(b, block_b)
    return NK.negacyclic_mul(ap, bp, ring, block_b=block_b,
                             interpret=interpret)[:nb]


def eval_values(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext, *,
                block_b: int = NK.DEFAULT_BLOCK_B,
                interpret: bool | None = None) -> jax.Array:
    """Kernel-backed centered eval values (Alg. 2 lines 2-4, no threshold).

    Returning the raw value lets callers apply their own decode threshold
    — the db executor thresholds per-atom (ε-tolerant CKKS equality) on
    ONE fused launch instead of one launch per distinct ε.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    params, rng = ks.params, ks.ring
    d = ct_sub(rng, ct0, ct1)
    d0p, b = _pad_batch(d.c0, block_b)
    d1p, _ = _pad_batch(d.c1, block_b)
    if params.mode == "paper":
        cek_br = CK.cek_to_br(ks)
        coeff0 = CK.eval_coeff0_paper(d0p, d1p, cek_br, rng, params.scale,
                                      block_b=block_b, interpret=interpret)
    else:
        digits = digit_decompose(params, d1p)          # [B, K, D, n]
        Bb = digits.shape[0]
        E = params.num_towers * params.gadget_digits_per_tower
        # rows: (k_src, digit) pairs; broadcast digit value to all towers
        dig = digits.reshape(Bb, E, 1, params.n)
        dig = jnp.broadcast_to(dig, (Bb, E, params.num_towers, params.n))
        cek_br = CK.cek_gadget_to_br(ks)
        coeff0 = CK.eval_coeff0_gadget(d0p, dig, cek_br, rng, params.scale,
                                       block_b=block_b, interpret=interpret)
    return R.crt_centered(params, coeff0[:b])


def compare(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext, *,
            block_b: int = NK.DEFAULT_BLOCK_B,
            interpret: bool | None = None) -> jax.Array:
    """Kernel-backed Algorithm 2 (-1/0/+1). Batched over leading dim."""
    v = eval_values(ks, ct0, ct1, block_b=block_b, interpret=interpret)
    return jnp.where(jnp.abs(v) < ks.params.tau,
                     0, jnp.sign(v)).astype(jnp.int32)


def broadcast_eval_values(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext, *,
                          block_b: int = NK.DEFAULT_BLOCK_B,
                          interpret: bool | None = None) -> jax.Array:
    """Kernel-backed raw eval values over two-sided-broadcast batch dims.

    ct0 and ct1 carry mutually-broadcastable batch shapes — e.g. the
    join tile layout ct0 [T, 1, K, n] against ct1 [1, R, K, n], or a
    shard_map body's local [S_r, 1, N_r] bounds against [1, N_l, 1]
    rows.  The broadcast grid is materialized once, flattened through
    the fused `cmp_eval` kernel path exactly like the single-dim entry,
    and reshaped back — ONE kernel launch with the same block padding
    rules as a fused filter scan.  THE shared broadcast-flatten-eval
    implementation: `db.join`'s tiled grids and `shard_eval_values`'
    per-device body both route here rather than re-deriving the
    reshape.  (Distinct from `db.join.pair_eval_values`, which adds
    host-side tiling on top of launches like this one.)
    """
    batch = jnp.broadcast_shapes(ct0.c0.shape[:-2], ct1.c0.shape[:-2])
    full = batch + ct0.c0.shape[-2:]
    flat = lambda x: jnp.broadcast_to(x, full).reshape(  # noqa: E731
        (-1,) + full[-2:])
    v = eval_values(ks, Ciphertext(flat(ct0.c0), flat(ct0.c1)),
                    Ciphertext(flat(ct1.c0), flat(ct1.c1)),
                    block_b=block_b, interpret=interpret)
    return v.reshape(batch)


# ---------------------------------------------------------------------------
# shard-aware eval entry (repro.db.shard)
# ---------------------------------------------------------------------------

def shard_eval_values(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext, *,
                      mesh, axis_name: str = "shard",
                      use_kernel: bool = False,
                      sel: jax.Array | None = None,
                      block_b: int = NK.DEFAULT_BLOCK_B,
                      interpret: bool | None = None) -> jax.Array:
    """Shard-parallel raw eval values under `shard_map`.

    ct0 leads with the shard dim — [S, ...batch, K, n], S divisible by
    the mesh's `axis_name` size; ct1 is replicated to every device and
    broadcast against ct0's batch dims inside each shard.  The two
    batch shapes broadcast TWO-SIDED, which covers both launch layouts
    the sharded engine uses: the fused filter stage (ct0 [S, A, N_sp],
    ct1 [A, 1] trapdoor bounds) and the cross-shard join pair grid
    (ct0 [S_l, 1, N_l, 1], ct1 [S_r, 1, N_r] — every device evaluates
    its left blocks against ALL right shard blocks).  HADES eval is
    row-local, so the mapped program needs NO cross-shard collectives —
    each device runs the eval pipeline over its own rows and only the
    decoded masks are reduced host-side.  `use_kernel=True` routes the
    per-device compute through the Pallas `cmp_eval` path (flattening
    local batch dims the way the single-device kernel entry does).

    `sel` supports the deduped fused-scan layout: ct0 carries UNIQUE
    columns [S, U, ...] and `sel` is the [A] per-atom gather into that
    unique axis (axis 1), applied INSIDE the mapped program — host-side
    bytes moved stay U·N while the program still evaluates all A atom
    lanes against the replicated [A, 1] bounds.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    fn = _shard_eval_fn(ks, mesh, axis_name, use_kernel, interpret, block_b,
                        ct0.c0.ndim, ct1.c0.ndim, sel is not None)
    args = (ct0.c0, ct0.c1, ct1.c0, ct1.c1)
    if sel is not None:
        args += (jnp.asarray(sel),)
    return fn(*args)


def _shard_eval_fn(ks: KeySet, mesh, axis_name: str, use_kernel: bool,
                   interpret: bool, block_b: int, nd0: int, nd1: int,
                   with_sel: bool):
    """The jitted `shard_map` program `shard_eval_values` launches,
    cached per KeySet and signature (the compile rehearsal lowers it
    directly)."""
    from jax.sharding import PartitionSpec as P

    from repro.core import compare as C

    def local_eval(c00, c01, b0, b1, *sel_arg):
        # stored blocks are int32 (`db.table.store`): widen inside the
        # program, where the conversion is as large as the local tile
        c00, c01, b0, b1 = (x.astype(jnp.int64) for x in (c00, c01, b0, b1))
        if sel_arg:
            c00 = jnp.take(c00, sel_arg[0], axis=1)
            c01 = jnp.take(c01, sel_arg[0], axis=1)
        if not use_kernel:
            return C.eval_value(ks, Ciphertext(c00, c01),
                                Ciphertext(b0, b1))
        return broadcast_eval_values(ks, Ciphertext(c00, c01),
                                     Ciphertext(b0, b1),
                                     block_b=block_b, interpret=interpret)

    cache = ks.cache("shard_eval")
    key = (id(mesh), axis_name, use_kernel, interpret, block_b, nd0, nd1,
           with_sel)
    if key not in cache:
        spec0 = P(axis_name, *([None] * (nd0 - 1)))
        rep = P(*([None] * nd1))
        in_specs = [spec0, spec0, rep, rep]
        if with_sel:
            in_specs.append(P(None))         # gather indices: replicated
        out_spec = P(axis_name, *([None] * (nd0 - 3)))
        fn = jax.shard_map(local_eval, mesh=mesh,
                           in_specs=tuple(in_specs),
                           out_specs=out_spec, check_vma=False)
        cache[key] = jax.jit(fn)
    return cache[key]
