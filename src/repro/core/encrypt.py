"""Encryption / decryption (LPR public-key RLWE) + Algorithm 3 (FAE).

Encoding (DESIGN.md §1.2): operands live in the constant coefficient,
payload = Δ_enc * m (BFV: m integer, |m| < t; CKKS: m real, payload =
round(m * Δ_enc)).  The HADES compare path later multiplies the phase by
`scale`, so Δ_enc deliberately leaves headroom: scale*Δ_enc*|m0-m1| < Q/2.

Algorithm 3 (perturbation-aware / FAE) adds Δ_m ~ U(-ε, ε) in plaintext
units plus an extra bounded noise e_m before encrypting, so equal
plaintexts yield statistically independent ciphertexts AND independent
compare outcomes (the equality-obfuscation property tested in
tests/test_fae.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ring as R
from repro.core import sampling
from repro.core.keys import KeySet
from repro.core.params import HadesParams


class Ciphertext(NamedTuple):
    """RLWE ciphertext (c0, c1), each [..., K, n].  2 components, no
    expansion for comparability (paper §3.4)."""
    c0: jax.Array
    c1: jax.Array

    def __sub__(self, other: "Ciphertext") -> "Ciphertext":
        raise TypeError("use compare.ct_sub(ring, a, b) — needs the modulus")


def _payload(params: HadesParams, m: jax.Array) -> jax.Array:
    """Scaled plaintext payload (integer, possibly negative). m: [...]."""
    if params.profile.scheme == "bfv":
        m_int = m.astype(jnp.int64)
        return m_int * params.delta_enc
    # ckks: fixed-point encode
    return jnp.round(m.astype(jnp.float64) * params.delta_enc).astype(jnp.int64)


def _encrypt_payload(ks: KeySet, payload: jax.Array,
                     key: jax.Array) -> Ciphertext:
    """payload: [...] integer -> ct with batch shape [...]."""
    params, rng = ks.params, ks.ring
    batch = payload.shape
    k_u, k_e0, k_e1 = jax.random.split(key, 3)
    u = sampling.ternary_poly(params, k_u, batch)      # [..., K, n]
    e0 = sampling.noise_poly(params, k_e0, batch)
    e1 = sampling.noise_poly(params, k_e1, batch)
    m_poly = R.const_poly(params, payload)             # [..., K, n]
    # pk0*u and pk1*u share u's forward transform and one inverse
    # transform over both products
    pk_ntt = ks.pk_ntt
    if pk_ntt is None:
        pk_ntt = R.ntt(rng, jnp.stack([ks.pk0, ks.pk1]))
    u_ntt = R.ntt(rng, u)
    prods = R.intt(rng, R.pointwise_mul(
        rng, pk_ntt.reshape((2,) + (1,) * len(batch) + pk_ntt.shape[1:]),
        u_ntt[None]))                                  # [2, ..., K, n]
    c0 = R.add(rng, R.add(rng, prods[0], e0), m_poly)
    c1 = R.add(rng, prods[1], e1)
    return Ciphertext(c0=c0, c1=c1)


def encrypt(ks: KeySet, m: jax.Array, key: jax.Array) -> Ciphertext:
    """Basic encryption (EncBasic). m: scalar or batch of operands."""
    m = jnp.asarray(m)
    return _encrypt_payload(ks, _payload(ks.params, m), key)


def encrypt_fae(ks: KeySet, m: jax.Array, key: jax.Array) -> Ciphertext:
    """Algorithm 3: perturbation-aware encryption (EncFAE).

    line 2: m_scaled = m * Δ_enc
    line 3: Δ_m ~ U(-ε, ε)
    line 4: m_perturbed = m_scaled + Δ_m * Δ_enc
    line 5/6: + e_m  (extra bounded noise on the payload)
    line 7: Encrypt(pk, ·)
    """
    params = ks.params
    m = jnp.asarray(m)
    k_pert, k_em, k_enc = jax.random.split(key, 3)
    base = _payload(params, m)
    pert = jax.random.uniform(
        k_pert, m.shape, dtype=jnp.float64,
        minval=-params.epsilon, maxval=params.epsilon)
    pert_int = jnp.round(pert * params.delta_enc).astype(jnp.int64)
    e_m = jax.random.randint(k_em, m.shape, -params.noise_bound,
                             params.noise_bound + 1, dtype=jnp.int64)
    return _encrypt_payload(ks, base + pert_int + e_m, k_enc)


def decrypt_raw(ks: KeySet, ct: Ciphertext) -> jax.Array:
    """Centered phase of coefficient 0: Δ_enc*m + noise.  [...] int64."""
    rng = ks.ring
    phase = R.add(rng, ct.c0, R.negacyclic_mul(rng, ct.c1, ks.sk))
    coeff0 = phase[..., :, 0]                       # [..., K]
    return R.crt_centered(ks.params, coeff0)


def decrypt(ks: KeySet, ct: Ciphertext) -> jax.Array:
    """Recover m (exact for BFV given |noise| < Δ_enc/2; approx for CKKS)."""
    v = decrypt_raw(ks, ct)
    params = ks.params
    if params.profile.scheme == "bfv":
        half = params.delta_enc // 2
        return (v + half) // params.delta_enc
    return v.astype(jnp.float64) / params.delta_enc


def noise_magnitude(ks: KeySet, ct: Ciphertext, m: jax.Array) -> jax.Array:
    """|phase - Δ_enc*m|: the live noise budget of a ciphertext (noise.py
    uses this for the §4.4 correctness predicates)."""
    v = decrypt_raw(ks, ct)
    return jnp.abs(v - _payload(ks.params, jnp.asarray(m)))
