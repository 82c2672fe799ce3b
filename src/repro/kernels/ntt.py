"""Pallas negacyclic-NTT kernels (interpret mode; validated against ref.py).

* A ciphertext block stays in VMEM across all log2(n) butterfly stages —
  one HBM round-trip per polynomial instead of one per stage.
* No bit-reversal gathers: the forward transform is decimation-in-frequency
  (natural -> bit-reversed "br-eval" order) and the inverse is
  decimation-in-time (br-eval -> natural); pointwise products are
  order-agnostic, so the convolution pipeline never permutes.
* Modular arithmetic: residues < 2^31 so a*b fits int64, and `%` is exact
  in interpret mode.  The TPU compiler refuses int64 vectors in a Pallas
  kernel (see kernels/ops.py), and a (bb, 1, n) block over K=2 towers
  breaks its (8, 128) block rule, so these kernels do not run on a TPU.
  A TPU version needs 32-bit residues (16-bit limbs with int32 MACs).

Layout: polys are [B, K, n] (batch, RNS towers, coeffs). Grid = (B/bb, K);
each program transforms a (bb, n) tile for one tower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ring as R

DEFAULT_BLOCK_B = 8


def _fwd_stages(x, stage_w, q, n):
    """DIF butterflies: natural order in -> br-eval order out. x: [bb, n]."""
    stages = n.bit_length() - 1
    for s in reversed(range(stages)):
        h = 1 << s
        m = 2 * h
        w = stage_w[s, :h]                       # [h]
        xr = x.reshape(-1, n // m, m)
        u, v = xr[..., :h], xr[..., h:]
        x = jnp.concatenate(
            [(u + v) % q, ((u - v) * w) % q], axis=-1).reshape(-1, n)
    return x


def _inv_stages(x, stage_w_inv, q, n):
    """DIT butterflies: br-eval order in -> natural order out."""
    stages = n.bit_length() - 1
    for s in range(stages):
        h = 1 << s
        m = 2 * h
        w = stage_w_inv[s, :h]
        xr = x.reshape(-1, n // m, m)
        u, v = xr[..., :h], xr[..., h:]
        t = (v * w) % q
        x = jnp.concatenate([(u + t) % q, (u - t) % q], axis=-1).reshape(-1, n)
    return x


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _ntt_kernel(x_ref, psi_ref, w_ref, q_ref, o_ref, *, n):
    q = q_ref[0]
    x = x_ref[:, 0, :]                            # [bb, n]
    x = (x * psi_ref[0]) % q                      # negacyclic pre-twist
    o_ref[:, 0, :] = _fwd_stages(x, w_ref[0], q, n)


def _intt_kernel(x_ref, psi_inv_ref, w_ref, q_ref, o_ref, *, n):
    q = q_ref[0]
    x = _inv_stages(x_ref[:, 0, :], w_ref[0], q, n)
    o_ref[:, 0, :] = (x * psi_inv_ref[0]) % q     # post-twist (n^-1 folded)


def _mul_kernel(a_ref, b_ref, psi_ref, psi_inv_ref, wf_ref, wi_ref, q_ref,
                o_ref, *, n):
    """Fused negacyclic multiply: twist -> DIF -> pointwise -> DIT -> twist.

    One kernel, one HBM round trip for a and b; zero gathers.
    """
    q = q_ref[0]
    a = (a_ref[:, 0, :] * psi_ref[0]) % q
    b = (b_ref[:, 0, :] * psi_ref[0]) % q
    a = _fwd_stages(a, wf_ref[0], q, n)
    b = _fwd_stages(b, wf_ref[0], q, n)
    prod = (a * b) % q
    out = _inv_stages(prod, wi_ref[0], q, n)
    o_ref[:, 0, :] = (out * psi_inv_ref[0]) % q


# ---------------------------------------------------------------------------
# pallas_call wrappers (shape plumbing only; public API in ops.py)
# ---------------------------------------------------------------------------

def _specs(bb: int, n: int, stages: int):
    """Common BlockSpecs: x-like [B,K,n], tables [K,...], q [K]."""
    x_spec = pl.BlockSpec((bb, 1, n), lambda i, k: (i, k, 0))
    psi_spec = pl.BlockSpec((1, n), lambda i, k: (k, 0))
    w_spec = pl.BlockSpec((1, stages, n // 2), lambda i, k: (k, 0, 0))
    q_spec = pl.BlockSpec((1,), lambda i, k: (k,))
    return x_spec, psi_spec, w_spec, q_spec


@functools.partial(jax.jit, static_argnames=("block_b", "interpret", "fwd"))
def ntt_br(x: jax.Array, ring: R.Ring, *, fwd: bool = True,
           block_b: int = DEFAULT_BLOCK_B, interpret: bool = True):
    """Forward (natural->br-eval) or inverse (br-eval->natural) NTT.

    x: [B, K, n] int64.  B must be a multiple of block_b (ops.py pads).
    """
    Bb, K, n = x.shape
    stages = n.bit_length() - 1
    bb = min(block_b, Bb)
    grid = (Bb // bb, K)
    x_spec, psi_spec, w_spec, q_spec = _specs(bb, n, stages)
    qs = ring.q_arr[:, 0]
    if fwd:
        kern = functools.partial(_ntt_kernel, n=n)
        tables = (ring.psi_pow, ring.stage_w, qs)
    else:
        kern = functools.partial(_intt_kernel, n=n)
        tables = (ring.psi_inv_pow, ring.stage_w_inv, qs)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec, psi_spec, w_spec, q_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, *tables)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def negacyclic_mul(a: jax.Array, b: jax.Array, ring: R.Ring, *,
                   block_b: int = DEFAULT_BLOCK_B, interpret: bool = True):
    """Fused a ⊛ b over [B, K, n] batches."""
    Bb, K, n = a.shape
    stages = n.bit_length() - 1
    bb = min(block_b, Bb)
    grid = (Bb // bb, K)
    x_spec, psi_spec, w_spec, q_spec = _specs(bb, n, stages)
    qs = ring.q_arr[:, 0]
    return pl.pallas_call(
        functools.partial(_mul_kernel, n=n),
        grid=grid,
        in_specs=[x_spec, x_spec, psi_spec, psi_spec, w_spec, w_spec, q_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, b, ring.psi_pow, ring.psi_inv_pow, ring.stage_w, ring.stage_w_inv,
      qs)
