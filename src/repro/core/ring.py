"""R_q = Z_q[x]/(x^n + 1) arithmetic in RNS form, pure JAX.

Polynomials are int64 arrays of shape [..., K, n] (K = number of RNS towers),
with residues kept in [0, q_k).  Every tower modulus is a prime in
(2^30, 2^31), so a product of two residues fits a signed int64.

This module owns modular arithmetic, and it never divides: an int64 `%`
is a runtime 64-bit division, which the TPU emulates, and its expansion
costs the TPU compiler about a second per op (a compare program held
hundreds of them).  Sums reduce by one conditional subtract, products by
Barrett reduction against mu = floor(2^62 / q) (`reduce`), and CRT
reconstruction runs in Garner form.  The results are the same canonical
residues `%` gives.  This module is also the *reference oracle* for the
Pallas NTT kernels (kernels/ref.py re-exports it).

The NTT is the standard negacyclic transform: pre-twist by psi^i, DIT
Cooley-Tukey forward, Gentleman-Sande inverse, post-twist by psi^-i * n^-1.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import HadesParams, NttTables


# ---------------------------------------------------------------------------
# division-free modular arithmetic (q, mu broadcast against x).  Each
# helper is jitted: under an outer jit it inlines, and called eagerly
# (keygen, client-side encryption) it is one program per shape rather
# than one per primitive.
# ---------------------------------------------------------------------------

def barrett_mu(qs) -> np.ndarray:
    """Barrett constants floor(2^62 / q) for moduli in (2^30, 2^31)."""
    qs = [int(q) for q in np.ravel(np.asarray(qs, dtype=object))]
    if not all((1 << 30) < q < (1 << 31) for q in qs):
        raise ValueError(f"tower moduli must lie in (2^30, 2^31): {qs}")
    return np.asarray([(1 << 62) // q for q in qs], dtype=np.int64)


@jax.jit
def csub(x: jax.Array, q) -> jax.Array:
    """x in [0, 2q) -> x mod q."""
    return jnp.where(x >= q, x - q, x)


@jax.jit
def reduce(x: jax.Array, q, mu) -> jax.Array:
    """x in [0, 2^62) -> x mod q, with mu = floor(2^62 / q).

    The quotient estimate floor((x >> 31) * mu / 2^31) never exceeds
    x / q and falls short of it by less than 4 (q > 2^30), so the
    remainder lands in [0, 4q) and three conditional subtracts finish.
    (x >> 31) * mu < 2^31 * 2^32 stays inside int64."""
    r = x - (((x >> 31) * mu) >> 31) * q
    for _ in range(3):
        r = csub(r, q)
    return r


@jax.jit
def reduce_signed(x: jax.Array, q, mu) -> jax.Array:
    """x with |x| < 2^62 -> x mod q in [0, q)."""
    r = reduce(jnp.abs(x), q, mu)
    return jnp.where((x < 0) & (r != 0), q - r, r)


@jax.jit
def mulmod(a: jax.Array, b, q, mu) -> jax.Array:
    """a * b mod q for residues a, b in [0, q)."""
    return reduce(a * b, q, mu)


@jax.jit
def addmod(a: jax.Array, b: jax.Array, q) -> jax.Array:
    return csub(a + b, q)


@jax.jit
def submod(a: jax.Array, b: jax.Array, q) -> jax.Array:
    d = a - b
    return jnp.where(d < 0, d + q, d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Ring:
    """Device-side ring context. Static metadata + jnp twiddle tables.

    Registered as a pytree (qs/n static) so jit'd kernels can close over it
    or take it as an argument.
    """

    qs: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    q_arr: jax.Array = None          # [K, 1] int64
    psi_pow: jax.Array = None        # [K, n]
    psi_inv_pow: jax.Array = None    # [K, n]
    stage_w: jax.Array = None        # [K, S, n/2]
    stage_w_inv: jax.Array = None    # [K, S, n/2]
    bitrev: jax.Array = None         # [n]
    mu_arr: jax.Array = None         # [K, 1] Barrett constants

    @property
    def num_towers(self) -> int:
        return len(self.qs)

    @property
    def stages(self) -> int:
        return self.n.bit_length() - 1


def make_ring(params: HadesParams) -> Ring:
    t: NttTables = params.ntt_tables()
    return Ring(
        qs=tuple(params.qs),
        n=params.n,
        q_arr=jnp.asarray(np.asarray(params.qs, dtype=np.int64)[:, None]),
        psi_pow=jnp.asarray(t.psi_pow),
        psi_inv_pow=jnp.asarray(t.psi_inv_pow),
        stage_w=jnp.asarray(t.stage_w),
        stage_w_inv=jnp.asarray(t.stage_w_inv),
        bitrev=jnp.asarray(t.bitrev),
        mu_arr=jnp.asarray(barrett_mu(params.qs)[:, None]),
    )


# ---------------------------------------------------------------------------
# elementwise ring ops (operands are canonical residues [..., K, n])
# ---------------------------------------------------------------------------

def add(ring: Ring, a: jax.Array, b: jax.Array) -> jax.Array:
    return addmod(a, b, ring.q_arr)


def sub(ring: Ring, a: jax.Array, b: jax.Array) -> jax.Array:
    return submod(a, b, ring.q_arr)


def neg(ring: Ring, a: jax.Array) -> jax.Array:
    return jnp.where(a == 0, a, ring.q_arr - a)


def scalar_mul(ring: Ring, a: jax.Array, s: jax.Array | int) -> jax.Array:
    """a * s mod q, s an int64 scalar already reduced below 2^30."""
    return mulmod(a, jnp.int64(s), ring.q_arr, ring.mu_arr)


def pointwise_mul(ring: Ring, a: jax.Array, b: jax.Array) -> jax.Array:
    return mulmod(a, b, ring.q_arr, ring.mu_arr)


# ---------------------------------------------------------------------------
# NTT (pure-jnp reference implementation)
# ---------------------------------------------------------------------------

def _dit_stages(a: jax.Array, stage_w: jax.Array, q: jax.Array,
                mu: jax.Array, n: int) -> jax.Array:
    """Forward DIT butterflies on bit-reversed input. a: [..., K, n]."""
    stages = n.bit_length() - 1
    q, mu = q[..., None], mu[..., None]
    for s in range(stages):
        h = 1 << s
        m = h * 2
        w = stage_w[:, s, :h]                      # [K, h]
        x = a.reshape(a.shape[:-1] + (n // m, m))
        u = x[..., :h]                             # [..., K, n/m, h]
        v = x[..., h:]
        t = mulmod(v, w[:, None, :], q, mu)
        a = jnp.concatenate([addmod(u, t, q), submod(u, t, q)], axis=-1)
        a = a.reshape(a.shape[:-2] + (n,))
    return a


def _gs_stages(a: jax.Array, stage_w_inv: jax.Array, q: jax.Array,
               mu: jax.Array, n: int) -> jax.Array:
    """Inverse Gentleman-Sande butterflies, natural-order input."""
    stages = n.bit_length() - 1
    q, mu = q[..., None], mu[..., None]
    for s in reversed(range(stages)):
        h = 1 << s
        m = h * 2
        w = stage_w_inv[:, s, :h]
        x = a.reshape(a.shape[:-1] + (n // m, m))
        u = x[..., :h]
        v = x[..., h:]
        a = jnp.concatenate([addmod(u, v, q),
                             mulmod(submod(u, v, q), w[:, None, :], q, mu)],
                            axis=-1)
        a = a.reshape(a.shape[:-2] + (n,))
    return a


@jax.jit
def ntt(ring: Ring, a: jax.Array) -> jax.Array:
    """Negacyclic forward NTT. a: [..., K, n] -> [..., K, n] (eval domain)."""
    a = pointwise_mul(ring, a, ring.psi_pow)           # pre-twist
    a = jnp.take(a, ring.bitrev, axis=-1)
    return _dit_stages(a, ring.stage_w, ring.q_arr, ring.mu_arr, ring.n)


@jax.jit
def intt(ring: Ring, a: jax.Array) -> jax.Array:
    """Negacyclic inverse NTT (includes n^-1 scaling)."""
    a = _gs_stages(a, ring.stage_w_inv, ring.q_arr, ring.mu_arr, ring.n)
    a = jnp.take(a, ring.bitrev, axis=-1)
    return pointwise_mul(ring, a, ring.psi_inv_pow)    # post-twist * n^-1


def negacyclic_mul(ring: Ring, a: jax.Array, b: jax.Array) -> jax.Array:
    """a * b in R_q via NTT."""
    return intt(ring, pointwise_mul(ring, ntt(ring, a), ntt(ring, b)))


def coeff0_reversal(ring: Ring, b: jax.Array) -> jax.Array:
    """r with coefficient 0 of a * b equal to sum_i a_i r_i (mod q).

    Coefficient 0 of a negacyclic product is a_0 b_0 - sum_{i>=1}
    a_i b_{n-i}, so r_0 = b_0 and r_i = -b_{n-i}.  b: [..., K, n]."""
    flipped = jnp.concatenate([b[..., :1], jnp.flip(b[..., 1:], axis=-1)],
                              axis=-1)
    return jnp.concatenate([flipped[..., :1], neg(ring, flipped[..., 1:])],
                           axis=-1)


def naive_negacyclic_mul(ring: Ring, a: jax.Array, b: jax.Array) -> jax.Array:
    """O(n^2) schoolbook negacyclic product — oracle for the NTT itself.

    Only for tests with small n. a, b: [K, n].
    """
    n = ring.n
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    k = (i + j) % n
    sign = jnp.where(i + j >= n, -1, 1).astype(jnp.int64)
    # out[k] = sum_{i+j = k mod n} sign * a[i]*b[j]; accumulate per tower
    # with mod after each outer-product row to stay inside int64.
    def tower(a_k, b_k, q):
        prod = (a_k[:, None] * b_k[None, :]) % q          # [n, n]
        contrib = (sign * prod) % q
        out = jnp.zeros((n,), jnp.int64)
        flat_k = k.reshape(-1)
        out = out.at[flat_k].add(contrib.reshape(-1) % q)
        return out % q
    outs = [tower(a[t], b[t], ring.qs[t]) for t in range(ring.num_towers)]
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# CRT decode (centered representative of a coefficient mod Q)
# ---------------------------------------------------------------------------

def crt_centered(params: HadesParams, residues: jax.Array) -> jax.Array:
    """Reconstruct centered value in (-Q/2, Q/2] from residues [..., K].

    Garner's mixed-radix form: v_k = (r_k - x_{<k}) * (q_0...q_{k-1})^-1
    mod q_k, x = v_0 + q_0 (v_1 + q_1 (v_2 + ...)), exact for Q < 2^62
    (every partial sum stays below Q)."""
    qs = params.qs
    Q = params.Q
    assert Q < (1 << 62), "CRT decode needs Q < 2^62"
    mus = barrett_mu(qs)
    digits = [residues[..., 0]]
    for k in range(1, len(qs)):
        qk, muk = qs[k], int(mus[k])
        # x_{<k} mod q_k by Horner over the digits found so far
        acc = reduce(digits[-1], qk, muk)
        for j in range(k - 2, -1, -1):
            acc = addmod(mulmod(acc, qs[j] % qk, qk, muk),
                         reduce(digits[j], qk, muk), qk)
        prefix = 1
        for j in range(k):
            prefix *= qs[j]
        inv = pow(prefix % qk, qk - 2, qk)
        digits.append(mulmod(submod(residues[..., k], acc, qk), inv,
                             qk, muk))
    x = digits[-1]
    for k in range(len(qs) - 2, -1, -1):
        x = digits[k] + qs[k] * x
    return jnp.where(x > Q // 2, x - Q, x)


def to_rns(params: HadesParams, coeffs: np.ndarray) -> np.ndarray:
    """Host helper: integer coefficient array [..., n] -> residues [..., K, n]."""
    coeffs = np.asarray(coeffs, dtype=object)
    out = np.stack([np.asarray(coeffs % q, dtype=np.int64)
                    for q in params.qs], axis=-2)
    return out


def const_poly(params: HadesParams, value: jax.Array) -> jax.Array:
    """Embed integer scalar(s) as the constant coefficient of an RNS poly.

    value: [...] int64 (may be negative) -> [..., K, n].
    """
    K, n = params.num_towers, params.n
    qs = jnp.asarray(np.asarray(params.qs, dtype=np.int64))  # [K]
    res = reduce_signed(value[..., None], qs,
                        jnp.asarray(barrett_mu(params.qs)))  # [..., K]
    zeros = jnp.zeros(value.shape + (K, n), dtype=jnp.int64)
    return zeros.at[..., 0].set(res)
