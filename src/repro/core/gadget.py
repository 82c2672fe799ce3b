"""RNS-gadget CEK evaluation (DESIGN.md §1.1, mode="gadget").

Digit-decomposes ctΔ,1 before hitting the CEK so the key-noise contribution
stays bounded by  K*D * sqrt(n) * B * B_e  instead of wrapping mod Q:

    ctΔ,1  =  Σ_{k}  (ctΔ,1 mod q_k) · alpha_k                  (CRT)
           =  Σ_{k,j}  d_{k,j} · B^j · alpha_k,   ||d_{k,j}||_inf < B

    Σ_{k,j}  d_{k,j} ⊛ cek[k,j]  =  ctΔ,1 · sk · scale  +  Σ d⊛e   (mod Q)

Schedule (the part the Pallas kernel accelerates): forward-NTT all K*D digit
polys, MAC against the precomputed eval-domain CEK, one inverse NTT total.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ring as R
from repro.core.keys import KeySet
from repro.core.params import HadesParams


def digit_decompose(params: HadesParams, c1: jax.Array) -> jax.Array:
    """c1: [..., K, n] residues -> digits [..., K, D, n] in [0, B).

    Digits are tiny (< B <= 2^8 by default) so their RNS lift is the digit
    value itself in every tower.
    """
    D = params.gadget_digits_per_tower
    b = params.profile.gadget_log_base
    shifts = jnp.arange(D, dtype=jnp.int64) * b          # [D]
    mask = params.gadget_base - 1
    return (c1[..., :, None, :] >> shifts[None, :, None]) & mask


def gadget_keymul(ks: KeySet, c1: jax.Array) -> jax.Array:
    """Compute  c1 · sk · scale + (bounded noise)   via the gadget CEK.

    c1: [..., K, n]  ->  [..., K, n]
    """
    params, rng = ks.params, ks.ring
    K, n = params.num_towers, params.n
    D = params.gadget_digits_per_tower

    digits = digit_decompose(params, c1)                 # [..., K, D, n]
    # lift each digit poly to full RNS: value is < B so residue == value.
    # new axis ordering: [..., K_src, D, K_tower, n]
    dig_rns = jnp.broadcast_to(
        digits[..., :, :, None, :],
        digits.shape[:-1] + (K, n))
    flat = dig_rns.reshape(dig_rns.shape[:-4] + (K * D, K, n))
    dig_ntt = R.ntt(rng, flat)                           # [..., K*D, K, n]

    cek_ntt = ks.cek_gadget_ntt.reshape(K * D, K, n)     # [K*D, K, n]
    prod = R.pointwise_mul(rng, dig_ntt, cek_ntt)        # eval domain
    acc = R.reduce(jnp.sum(prod, axis=-3), rng.q_arr, rng.mu_arr)  # [..., K, n]
    return R.intt(rng, acc)
