"""Fused HADES Eval kernel (Alg. 2 / Alg. 4 hot path).

Computes, per batched ciphertext difference (d0, d1):

    paper mode :  coeff0 of [ d0*scale + d1 ⊛ cek ]         (mod q, per tower)
    gadget mode:  coeff0 of [ d0*scale + Σ_e digit_e ⊛ cek_e ]

entirely inside one kernel: pre-twist, DIF-NTT, MAC against the CEK held in
br-eval order, DIT-INTT, post-twist, emit coefficient 0 only.

The kernel emits one residue per tower instead of the n-coefficient eval
polynomial.  It computes on int64 vectors, which the TPU compiler refuses
(see kernels/ops.py), so it runs in interpret mode on the CPU only, where
ref.py is its oracle.  Its layout is not TPU-legal either: a (bb, 1, n)
block over K=2 towers breaks the (8, 128) rule for the last two block
dimensions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ring as R
from repro.core.keys import KeySet
from repro.kernels.ntt import _fwd_stages, _inv_stages

DEFAULT_BLOCK_B = 8


def _eval_paper_kernel(d0_ref, d1_ref, cek_ref, psi_ref, psi_inv_ref,
                       wf_ref, wi_ref, q_ref, scale_ref, o_ref, *, n):
    q = q_ref[0]
    scale = scale_ref[0]
    d1 = (d1_ref[:, 0, :] * psi_ref[0]) % q
    d1 = _fwd_stages(d1, wf_ref[0], q, n)
    prod = (d1 * cek_ref[0]) % q                    # cek already br-eval
    out = _inv_stages(prod, wi_ref[0], q, n)
    out = (out * psi_inv_ref[0]) % q
    # eval = d0*scale + d1 ⊛ cek ; decode -> coefficient 0 per tower
    o_ref[:, 0] = (d0_ref[:, 0, 0] * scale + out[:, 0]) % q


def _eval_gadget_kernel(d0_ref, dig_ref, cek_ref, psi_ref, psi_inv_ref,
                        wf_ref, wi_ref, q_ref, scale_ref, o_ref, *, n, E):
    """dig_ref: [bb, E, 1, n] digit polys (already < B, RNS-lift = identity);
    cek_ref: [E, 1, n] gadget CEK rows for this tower, br-eval order."""
    q = q_ref[0]
    scale = scale_ref[0]
    acc = jnp.zeros((dig_ref.shape[0], n), jnp.int64)
    for e in range(E):
        d = (dig_ref[:, e, 0, :] * psi_ref[0]) % q
        d = _fwd_stages(d, wf_ref[0], q, n)
        acc = (acc + (d * cek_ref[e, 0]) % q) % q   # MAC in eval domain
    out = _inv_stages(acc, wi_ref[0], q, n)
    out = (out * psi_inv_ref[0]) % q
    o_ref[:, 0] = (d0_ref[:, 0, 0] * scale + out[:, 0]) % q


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def eval_coeff0_paper(d0: jax.Array, d1: jax.Array, cek_br: jax.Array,
                      ring: R.Ring, scale: int, *,
                      block_b: int = DEFAULT_BLOCK_B,
                      interpret: bool = True) -> jax.Array:
    """[B, K, n] diff components + br-eval cek [K, n] -> coeff0 [B, K]."""
    Bb, K, n = d0.shape
    stages = n.bit_length() - 1
    bb = min(block_b, Bb)
    grid = (Bb // bb, K)
    x_spec = pl.BlockSpec((bb, 1, n), lambda i, k: (i, k, 0))
    tab_spec = pl.BlockSpec((1, n), lambda i, k: (k, 0))
    w_spec = pl.BlockSpec((1, stages, n // 2), lambda i, k: (k, 0, 0))
    q_spec = pl.BlockSpec((1,), lambda i, k: (k,))
    o_spec = pl.BlockSpec((bb, 1), lambda i, k: (i, k))
    scale_arr = jnp.full((K,), scale, jnp.int64)
    return pl.pallas_call(
        functools.partial(_eval_paper_kernel, n=n),
        grid=grid,
        in_specs=[x_spec, x_spec, tab_spec, tab_spec, tab_spec, w_spec,
                  w_spec, q_spec, q_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((Bb, K), jnp.int64),
        interpret=interpret,
    )(d0, d1, cek_br, ring.psi_pow, ring.psi_inv_pow, ring.stage_w,
      ring.stage_w_inv, ring.q_arr[:, 0], scale_arr)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def eval_coeff0_gadget(d0: jax.Array, digits: jax.Array,
                       cek_gadget_br: jax.Array, ring: R.Ring, scale: int, *,
                       block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool = True) -> jax.Array:
    """digits: [B, E, K, n] (E = K_src*D gadget rows, values < B_gadget);
    cek_gadget_br: [E, K, n] br-eval order.  Returns coeff0 [B, K]."""
    Bb, E, K, n = digits.shape
    stages = n.bit_length() - 1
    bb = min(block_b, Bb)
    grid = (Bb // bb, K)
    x_spec = pl.BlockSpec((bb, 1, n), lambda i, k: (i, k, 0))
    dig_spec = pl.BlockSpec((bb, E, 1, n), lambda i, k: (i, 0, k, 0))
    cek_spec = pl.BlockSpec((E, 1, n), lambda i, k: (0, k, 0))
    tab_spec = pl.BlockSpec((1, n), lambda i, k: (k, 0))
    w_spec = pl.BlockSpec((1, stages, n // 2), lambda i, k: (k, 0, 0))
    q_spec = pl.BlockSpec((1,), lambda i, k: (k,))
    o_spec = pl.BlockSpec((bb, 1), lambda i, k: (i, k))
    scale_arr = jnp.full((K,), scale, jnp.int64)
    return pl.pallas_call(
        functools.partial(_eval_gadget_kernel, n=n, E=E),
        grid=grid,
        in_specs=[x_spec, dig_spec, cek_spec, tab_spec, tab_spec, w_spec,
                  w_spec, q_spec, q_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((Bb, K), jnp.int64),
        interpret=interpret,
    )(d0, digits, cek_gadget_br, ring.psi_pow, ring.psi_inv_pow,
      ring.stage_w, ring.stage_w_inv, ring.q_arr[:, 0], scale_arr)


# ---------------------------------------------------------------------------
# br-eval-order CEK precompute helpers
# ---------------------------------------------------------------------------

def cek_to_br(ks: KeySet) -> jax.Array:
    """Paper-mode cek -> br-eval order [K, n] (DIF output order)."""
    ev = R.ntt(ks.ring, ks.cek)
    return jnp.take(ev, ks.ring.bitrev, axis=-1)


def cek_gadget_to_br(ks: KeySet) -> jax.Array:
    """Gadget CEK -> [E, K, n] br-eval order."""
    params = ks.params
    E = params.num_towers * params.gadget_digits_per_tower
    flat = ks.cek_gadget_ntt.reshape(E, params.num_towers, params.n)
    return jnp.take(flat, ks.ring.bitrev, axis=-1)
