"""Algorithm 1: Key Generation.

Outputs (pk, sk, cek).  Two CEK realizations (DESIGN.md §1.1):

* mode="paper"  : cek = sk*scale + e_cek — the literal Alg. 1 lines 5-8.
  Correct only while |<e_cek, ctΔ,1>| < scale/2 (the paper's own
  precondition, Thm 4.1), which for uniform ctΔ,1 forces ||e_cek|| ≈ 0;
  we therefore expose `paper_ecek_weight` (number of nonzero noise
  coefficients) so experiments can dial the correctness/security tension.

* mode="gadget" : RNS-gadget CEK, cek[k,j] = B^j * alpha_k * sk * scale + e
  (key-switching form).  Comparisons stay correct with full-strength noise
  because Eval digit-decomposes ctΔ,1 first (gadget.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ring as R
from repro.core import sampling
from repro.core.params import HadesParams


@dataclasses.dataclass
class KeySet:
    params: HadesParams
    ring: R.Ring
    sk: jax.Array                      # [K, n] (ternary, RNS-lifted)
    pk0: jax.Array                     # [K, n]  -(a*sk + e_pk)
    pk1: jax.Array                     # [K, n]  a
    cek: Optional[jax.Array]           # paper mode: [K, n]
    cek_gadget: Optional[jax.Array]    # gadget mode: [K_src, D, K, n]
    cek_gadget_ntt: Optional[jax.Array]  # same, eval domain (precomputed)
    # coefficient-0 form of the CEK (`ring.coeff0_reversal`): paper
    # [K, n], gadget [K_src*D, K, n].  Eval reads one coefficient of the
    # key product, an inner product against this.
    cek_rev: Optional[jax.Array] = None
    # eval-domain public key [2, K, n] (pk0, pk1): encryption transforms
    # only its randomness
    pk_ntt: Optional[jax.Array] = None

    @property
    def mode(self) -> str:
        return self.params.mode

    def cache(self, name: str) -> dict:
        """A dict stashed on this keyset, for jitted programs that close
        over it (their lifetime is the keyset's): the one per-KeySet
        cache every layer uses."""
        return self.__dict__.setdefault(f"_cache_{name}", {})


def _gadget_cek(params: HadesParams, rng: R.Ring, sk: jax.Array,
                key: jax.Array) -> jax.Array:
    """cek[k_src, j] = alpha_{k_src} * B^j * scale * sk + e  (mod Q), RNS.

    alpha_k = (Q/q_k) * [(Q/q_k)^{-1}]_{q_k}: the CRT lifting constant, so
    that sum_k (c1 mod q_k) * alpha_k = c1 (mod Q).  Each entry is a full
    RNS polynomial [K, n].
    """
    K, n = params.num_towers, params.n
    D = params.gadget_digits_per_tower
    B = params.gadget_base
    alphas = params.crt_alphas()
    scale = params.scale

    entries = []
    keys = jax.random.split(key, K * D)
    for k_src in range(K):
        for j in range(D):
            # host-side big-int constant:  alpha_k * B^j * scale  mod Q
            c = (alphas[k_src] * pow(B, j) % params.Q) * scale % params.Q
            # reduce into each tower
            c_rns = jnp.asarray(
                np.asarray([c % q for q in params.qs], dtype=np.int64)
            )[:, None]                                   # [K, 1]
            e = sampling.noise_poly(params, keys[k_src * D + j])
            entry = R.add(rng, R.pointwise_mul(rng, sk, c_rns), e)
            entries.append(entry)
    return jnp.stack(entries).reshape(K, D, K, n)


def keygen(params: HadesParams, key: jax.Array,
           paper_ecek_weight: Optional[int] = None) -> KeySet:
    """Algorithm 1.  paper_ecek_weight: #nonzero coeffs of e_cek (paper mode);
    None => full-density U(-B_e,B_e) noise exactly as written."""
    rng = R.make_ring(params)
    k_sk, k_a, k_epk, k_cek, k_g = jax.random.split(key, 5)

    sk = sampling.ternary_poly(params, k_sk)                       # line 1
    a = sampling.uniform_poly(params, k_a)                         # line 2
    e_pk = sampling.noise_poly(params, k_epk)                      # line 3
    pk0 = R.neg(rng, R.add(rng, R.negacyclic_mul(rng, a, sk), e_pk))  # line 4

    # line 5: scale > max(2*B_e, ||sk||_inf) — checked statically.
    assert params.scale > max(2 * params.noise_bound, 1), \
        "profile violates Alg.1 line 5 scale condition"

    cek = None
    cek_gadget = None
    cek_gadget_ntt = None
    if params.mode == "paper":
        e_cek = sampling.noise_poly(params, k_cek)                 # line 6
        if paper_ecek_weight is not None:
            # keep only the first `weight` coefficients of the noise — the
            # knob for the §1.1 correctness/security study.
            mask = (jnp.arange(params.n) < paper_ecek_weight)
            e_cek = e_cek * mask
        sk_scaled = R.scalar_mul(rng, sk, params.scale)            # line 7
        cek = R.add(rng, sk_scaled, e_cek)                         # line 8
        cek_rev = R.coeff0_reversal(rng, cek)
    else:
        cek_gadget = _gadget_cek(params, rng, sk, k_g)
        # Precompute the eval-domain form: Eval does (digit ⊛ cek) products,
        # so keeping cek in NTT form saves one forward NTT per entry/compare.
        flat = cek_gadget.reshape(-1, params.num_towers, params.n)
        cek_gadget_ntt = R.ntt(rng, flat).reshape(cek_gadget.shape)
        cek_rev = R.coeff0_reversal(rng, flat)

    return KeySet(params=params, ring=rng, sk=sk, pk0=pk0, pk1=a,
                  cek=cek, cek_gadget=cek_gadget,
                  cek_gadget_ntt=cek_gadget_ntt, cek_rev=cek_rev,
                  pk_ntt=R.ntt(rng, jnp.stack([pk0, a])))
