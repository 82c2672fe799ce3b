"""Polynomial samplers (paper §4.2 "Noise in Cryptography").

All samplers are counter-based (jax.random), so distributed workers can
regenerate any sample deterministically from (seed, role, index) — this is
what makes checkpoints/elastic restarts replayable (DESIGN.md §5).

Note on the secret key: Alg. 1 line 1 says "uniformly from the ring", but
line 5 requires scale > ||sk||_inf, which is unsatisfiable for a uniform
sk (||sk||_inf ~ q/2).  We follow standard RLWE practice (and the paper's
own OpenFHE backend) and sample sk ternary, making ||sk||_inf = 1 and the
scale condition trivially satisfiable.  Recorded as a deviation in
DESIGN.md §7.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.params import HadesParams


def uniform_poly(params: HadesParams, key: jax.Array,
                 shape: tuple = ()) -> jax.Array:
    """Uniform element of R_q (per-tower uniform residues). [..., K, n]."""
    keys = jax.random.split(key, params.num_towers)
    cols = []
    for k, q in enumerate(params.qs):
        cols.append(jax.random.randint(
            keys[k], shape + (params.n,), 0, q, dtype=jnp.int64))
    return jnp.stack(cols, axis=-2)


def _small_to_rns(params: HadesParams, small: jax.Array) -> jax.Array:
    """Lift a small signed integer poly [..., n] into RNS [..., K, n]
    (|coefficients| < q)."""
    import numpy as np
    qs = jnp.asarray(np.asarray(params.qs, dtype=jnp.int64))[:, None]  # [K, 1]
    lifted = small[..., None, :]
    return jnp.where(lifted < 0, lifted + qs, lifted)


def ternary_poly(params: HadesParams, key: jax.Array,
                 shape: tuple = ()) -> jax.Array:
    """sk / encryption randomness u: coefficients in {-1, 0, 1}."""
    small = jax.random.randint(key, shape + (params.n,), -1, 2,
                               dtype=jnp.int64)
    return _small_to_rns(params, small)


def noise_poly(params: HadesParams, key: jax.Array,
               shape: tuple = (), bound: int | None = None) -> jax.Array:
    """e ~ U(-B_e, B_e)^n per the paper; verified |e|_inf <= B_e by range."""
    b = params.noise_bound if bound is None else bound
    small = jax.random.randint(key, shape + (params.n,), -b, b + 1,
                               dtype=jnp.int64)
    return _small_to_rns(params, small)


def small_signed(params: HadesParams, key: jax.Array, shape: tuple,
                 bound: int) -> jax.Array:
    """Small signed integers (NOT lifted to RNS) — used for perturbations."""
    return jax.random.randint(key, shape, -bound, bound + 1, dtype=jnp.int64)
