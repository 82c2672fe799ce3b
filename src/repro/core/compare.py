"""Algorithms 2 & 4: HADES ciphertext comparison + database operations.

    ctΔ      = ct0 - ct1                      (component-wise, mod q)
    ct_eval  = ctΔ,0 * scale + ctΔ,1 ⊛ cek    (paper mode)
             = ctΔ,0 * scale + GadgetKeyMul(ctΔ,1)   (gadget mode)
    value    = CRT-centered coefficient 0 of ct_eval
    Alg. 2   -> -1 / 0 / +1   with |value| < τ  =>  0
    Alg. 4   -> strict bool (m_a > m_b); equality obfuscated by FAE noise

Everything is batched: ciphertext components carry arbitrary leading batch
dims, so a range query over 35k rows is ONE vectorized eval (paper §5.3's
O(n) comparison claim — here it is also a single XLA program).

Database ops built on the comparator (the primitives under `repro.db`):
  * range_query     — membership mask for lo <= m <= hi (ONE fused eval)
  * encrypted_sort  — bitonic network (data-independent => jit/TPU friendly);
                      non-power-of-two columns are padded with encrypted
                      sentinel rows that are stripped from the output
  * encrypted_topk  — partial bitonic top-k network, O(n log^2 k) compares
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ckks as _CK
from repro.core import encrypt as _E
from repro.core import gadget
from repro.core import ring as R
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet

# Fixed public-key randomness for server-side sentinel padding rows.  The
# pad rows carry no secret (their value is the public +/-max_operand bound),
# so a static key only fixes *which* valid encryption of the sentinel is
# appended — callers that care can pass their own `pad_key`.
_PAD_KEY_SEED = 0x4ADE5


# ---------------------------------------------------------------------------
# the Eval primitive
# ---------------------------------------------------------------------------

def ct_sub(rng: R.Ring, a: Ciphertext, b: Ciphertext) -> Ciphertext:
    return Ciphertext(R.sub(rng, a.c0, b.c0), R.sub(rng, a.c1, b.c1))


def keyed_coeff0(ks: KeySet, c1: jax.Array) -> jax.Array:
    """Coefficient 0 of the CEK product of c1 [..., K, n] -> [..., K].

    paper : coeff0 of c1 ⊛ cek
    gadget: coeff0 of Σ_e digit_e(c1) ⊛ cek_e   (= gadget_keymul's)

    Decode reads coefficient 0 only, and coefficient 0 of a negacyclic
    product is one inner product with the key's reversal `ks.cek_rev`,
    so no NTT runs.  Gadget terms are digit (< 2^8) × residue (< 2^31),
    and E·n of them sum below 2^63 (2^54 at paper-bfv), so one
    reduction ends the sum; paper-mode terms are reduced products."""
    params, rng = ks.params, ks.ring
    q, mu = rng.q_arr[:, 0], rng.mu_arr[:, 0]
    if params.mode == "paper":
        terms = R.pointwise_mul(rng, c1, ks.cek_rev)            # [..., K, n]
        return R.reduce(jnp.sum(terms, axis=-1), q, mu)
    n = params.n
    digits = gadget.digit_decompose(params, c1)                # [..., K, D, n]
    digits = digits.reshape(digits.shape[:-3] + (-1, n))       # [..., E, n]
    s = jnp.sum(digits[..., :, None, :] * ks.cek_rev, axis=(-3, -1))
    return R.reduce(s, q, mu)


def eval_value(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext) -> jax.Array:
    """Centered integer eval value ≈ scale*Δ_enc*(m0-m1) + noise.  [...].

    Alg. 2: ctΔ = ct0 - ct1 (line 2), ct_eval = scale·ctΔ,0 + key
    product of ctΔ,1 (line 3), decode coefficient 0 (line 4) — computed
    on coefficient 0 alone (`keyed_coeff0`)."""
    params, rng = ks.params, ks.ring
    q, mu = rng.q_arr[:, 0], rng.mu_arr[:, 0]
    d0 = R.submod(ct0.c0[..., :, 0], ct1.c0[..., :, 0], q)    # line 2
    d1 = R.sub(rng, ct0.c1, ct1.c1)
    scaled = R.mulmod(d0, params.scale, q, mu)                 # line 3
    coeff0 = R.addmod(scaled, keyed_coeff0(ks, d1), q)         # line 4
    return R.crt_centered(params, coeff0)


def resolve_tau(ks: KeySet, eps: Optional[float]) -> int:
    """The decode threshold an ε-tolerance request resolves to.

    eps=None keeps the profile's native τ (BFV: integer tie semantics;
    CKKS: `ckks.equality_tolerance` precision semantics).  An explicit ε
    (plaintext units) widens the equality band: values within ε compare
    as 0.  ε below the noise floor clamps up to the native τ.
    """
    if eps is None:
        return ks.params.tau
    return _CK.eps_to_tau(ks.params, eps)


def three_way(ks: KeySet, v: jax.Array, *,
              eps: Optional[float] = None) -> jax.Array:
    """Alg. 2 line 5: eval value -> -1/0/+1 (τ-thresholded).

    `eps` widens the equality band to |m0-m1| <= ε (plaintext units) —
    the ε-tolerant semantics CKKS float columns need (the static τ_ε is
    closed over by jit, so per-ε compiled compares cache like the
    default)."""
    tau = resolve_tau(ks, eps)
    return jnp.where(jnp.abs(v) < tau, 0, jnp.sign(v)).astype(jnp.int32)


def compare(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext, *,
            eps: Optional[float] = None) -> jax.Array:
    """Algorithm 2: three-way comparison -1/0/+1 (τ-thresholded; `eps`
    optionally widens the equality band, see `three_way`)."""
    return three_way(ks, eval_value(ks, ct0, ct1), eps=eps)


def compare_fae(ks: KeySet, ct0: Ciphertext, ct1: Ciphertext) -> jax.Array:
    """Algorithm 4: strict bool m_a > m_b.  No equality outcome — on FAE
    ciphertexts of equal plaintexts the perturbation makes the answer an
    independent coin flip (tested property), which is exactly the paper's
    equality-obfuscation contract."""
    return eval_value(ks, ct0, ct1) > 0


def compare_many(ks: KeySet, cts_a: Ciphertext, cts_b: Ciphertext, *,
                 eps: Optional[float] = None) -> jax.Array:
    """Vectorized Alg. 2 over matching batch shapes."""
    return compare(ks, cts_a, cts_b, eps=eps)


# ---------------------------------------------------------------------------
# database operations
# ---------------------------------------------------------------------------

def _gather_ct(ct: Ciphertext, idx: jax.Array) -> Ciphertext:
    return Ciphertext(ct.c0[idx], ct.c1[idx])


def range_query(ks: KeySet, column: Ciphertext, ct_lo: Ciphertext,
                ct_hi: Ciphertext, *,
                eps: Optional[float] = None) -> jax.Array:
    """Mask of rows with lo <= m <= hi.  column: batched ct over N rows.

    Both bound comparisons run in ONE batched `eval_value` call: the bounds
    are stacked into a [2, 1] batch that broadcasts against the column's
    [N] rows, halving kernel launches on the hot path versus the naive
    compare-vs-lo + compare-vs-hi pipeline.

    `eps` widens the boundary tolerance on float (CKKS) columns: rows
    within ε of a bound count as inside (ε-inclusive bounds).
    """
    bounds = Ciphertext(
        jnp.stack([ct_lo.c0, ct_hi.c0])[:, None],    # [2, 1, K, n]
        jnp.stack([ct_lo.c1, ct_hi.c1])[:, None])
    cmp = three_way(ks, eval_value(ks, column, bounds), eps=eps)   # [2, N]
    return (cmp[0] >= 0) & (cmp[1] <= 0)


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) (n >= 0).  THE pow2-padding
    geometry: table ingest, sort/top-k sentinel padding and the sharded
    merge networks all size their rows through this one helper, so their
    padded shapes can never drift apart.  n <= 1 returns 1 — the minimum
    block: an EMPTY column still pads to one slot, not two (naively,
    `(0 - 1).bit_length() == 1` would give 2), which is what lets empty
    tables and freshly-compacted delta runs share the ordinary geometry.
    A negative count is always a caller bug, never a geometry.
    """
    n = int(n)
    if n < 0:
        raise ValueError(f"row count must be >= 0, got {n}")
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _bitonic_pairs(n: int):
    """Yield (stage) index arrays for a bitonic sorting network over n=2^k."""
    import numpy as np
    k = n.bit_length() - 1
    for phase in range(1, k + 1):
        for sub in range(phase - 1, -1, -1):
            stride = 1 << sub
            i = np.arange(n)
            partner = i ^ stride
            first = i < partner
            # ascending iff bit `phase` of i is 0
            up = ((i >> phase) & 1) == 0
            lo = i[first]
            hi = partner[first]
            asc = up[first]
            yield (jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(asc))


def bitonic_compare_count(n: int) -> int:
    """Compare-exchanges the `encrypted_sort` network performs for an
    n-row column (after its padding to 2^ceil(log2 n)).  Kept next to
    `_bitonic_pairs` so stats/benchmark counts stay definitionally tied
    to the network actually run."""
    n_pad = next_pow2(n)
    stages = sum(range(1, n_pad.bit_length()))
    return stages * (n_pad // 2)


def _pad_to_pow2(ks: KeySet, column: Ciphertext, pad_value: int,
                 pad_key: Optional[jax.Array], *,
                 n_target: Optional[int] = None) -> Tuple[Ciphertext, int]:
    """Append encrypted `pad_value` sentinel rows up to the next power of
    two (or to an explicit power-of-two `n_target` — the sharded merge
    networks pad every shard's candidates to one common block size).
    Returns (padded column, original row count)."""
    n_rows = column.c0.shape[0]
    n_pad = n_target if n_target is not None else next_pow2(n_rows)
    assert n_pad >= n_rows and n_pad == next_pow2(n_pad)
    if n_pad == n_rows:
        return column, n_rows
    key = pad_key if pad_key is not None else jax.random.PRNGKey(_PAD_KEY_SEED)
    pad = _E.encrypt(ks, jnp.full((n_pad - n_rows,), pad_value, jnp.int64),
                     key)
    return Ciphertext(jnp.concatenate([column.c0, pad.c0]),
                      jnp.concatenate([column.c1, pad.c1])), n_rows


def _compare_swap(ks: KeySet, cmp: Callable, c0: jax.Array, c1: jax.Array,
                  perm: jax.Array, lo: jax.Array, hi: jax.Array,
                  asc: jax.Array):
    """One batched compare-exchange stage over index pairs (lo[i], hi[i]).

    asc[i] True  => the smaller plaintext lands at lo[i] (ascending pair);
    asc[i] False => the larger lands at lo[i].  ONE batched Eval per call,
    and one launch: the stage is a jitted program per (KeySet,
    comparator), so its gathers and scatters fuse instead of each
    holding a column-sized intermediate.
    """
    cache = ks.cache("swap_stage")
    stage = cache.get(cmp)
    if stage is None:
        stage = cache[cmp] = jax.jit(
            lambda *args: _compare_swap_stage(ks, cmp, *args))
    return stage(c0, c1, perm, jnp.asarray(lo), jnp.asarray(hi),
                 jnp.asarray(asc))


def _compare_swap_stage(ks: KeySet, cmp: Callable, c0, c1, perm, lo, hi,
                        asc):
    """`_compare_swap`'s body (traced once per stage shape)."""
    a = Ciphertext(c0[lo], c1[lo])
    b = Ciphertext(c0[hi], c1[hi])
    a_gt_b = cmp(ks, a, b)                                  # [pairs] bool
    swap = jnp.where(asc, a_gt_b, ~a_gt_b)
    sw = swap[:, None, None]
    new_lo0 = jnp.where(sw, b.c0, a.c0)
    new_lo1 = jnp.where(sw, b.c1, a.c1)
    new_hi0 = jnp.where(sw, a.c0, b.c0)
    new_hi1 = jnp.where(sw, a.c1, b.c1)
    c0 = c0.at[lo].set(new_lo0).at[hi].set(new_hi0)
    c1 = c1.at[lo].set(new_lo1).at[hi].set(new_hi1)
    p_lo, p_hi = perm[lo], perm[hi]
    perm = perm.at[lo].set(jnp.where(swap, p_hi, p_lo))
    perm = perm.at[hi].set(jnp.where(swap, p_lo, p_hi))
    return c0, c1, perm


def encrypted_sort(ks: KeySet, column: Ciphertext,
                   comparator: Callable | None = None, *,
                   pad_value: Optional[int] = None,
                   pad_key: Optional[jax.Array] = None,
                   ) -> Tuple[Ciphertext, jax.Array]:
    """Bitonic sort of a ciphertext column (ascending by plaintext).

    Returns (sorted ciphertexts, permutation).  The network is
    data-independent: each stage is ONE batched Eval over n/2 pairs —
    O(log^2 n) stages total, each embarrassingly parallel on the mesh.

    Non-power-of-two columns are padded with encrypted `pad_value` sentinel
    rows (default +max_operand//2: the compare path needs |value - sentinel|
    <= max_operand, so the default assumes |values| <= max_operand/2 — the
    regime every profile's datasets live in); the sentinels are stripped
    from both returned arrays, so the output always has exactly the input's
    row count.  Stripping selects by permutation id, not position, so real
    rows that happen to *equal* the sentinel (FAE ties order coin-flip)
    are still returned.  Callers with values above max_operand/2 should
    pass their own in-headroom `pad_value`.
    """
    cmp = comparator or compare_fae
    if pad_value is None:
        pad_value = ks.params.max_operand // 2
    column, n_rows = _pad_to_pow2(ks, column, pad_value, pad_key)
    n_padded = column.c0.shape[0]
    perm = jnp.arange(n_padded)
    c0, c1 = column.c0, column.c1
    for lo, hi, asc in _bitonic_pairs(n_padded):
        c0, c1, perm = _compare_swap(ks, cmp, c0, c1, perm, lo, hi, asc)
    if n_padded == n_rows:
        return Ciphertext(c0, c1), perm
    # real rows are the ones whose permutation id predates the padding;
    # exactly n_rows of them exist, in sorted order
    keep = jnp.nonzero(perm < n_rows, size=n_rows)[0]
    return Ciphertext(c0[keep], c1[keep]), perm[keep]


def _block_pairs(n_blocks: int, block: int, lo, hi, asc):
    """Tile block-local pair indices across n_blocks contiguous blocks."""
    import numpy as np
    base = (np.arange(n_blocks) * block)[:, None]
    glo = (base + np.asarray(lo)[None, :]).ravel()
    ghi = (base + np.asarray(hi)[None, :]).ravel()
    gasc = np.tile(np.asarray(asc), n_blocks)
    return jnp.asarray(glo), jnp.asarray(ghi), jnp.asarray(gasc)


def encrypted_topk(ks: KeySet, column: Ciphertext, k: int,
                   comparator: Callable | None = None, *,
                   pad_value: Optional[int] = None,
                   pad_key: Optional[jax.Array] = None,
                   ) -> Tuple[Ciphertext, jax.Array]:
    """Top-k by plaintext value (descending) via a partial bitonic top-k
    network — O(n log^2 k) compares instead of the O(n log^2 n) full sort.

    Tournament reduction (the standard GPU bitonic top-k):
      1. sort each contiguous block of kp = 2^ceil(log2 k) rows descending;
      2. max-merge block pairs: position i of block A against position
         kp-1-i of block B keeps the larger at A — A then holds a bitonic
         sequence containing the top-kp of A∪B;
      3. bitonic-merge each surviving block back to sorted descending
         (log kp stages), halve the block count, repeat.

    Every stage is ONE batched Eval.  Non-power-of-two columns are padded
    with encrypted `pad_value` sentinels (default -max_operand//2, losing
    every tournament round while staying inside the |a-b| <= max_operand
    compare headroom for |values| <= max_operand/2) which never reach the
    result, since k <= n_rows real rows exist.  A real row that *equals*
    the sentinel can tie its way out of the tournament (FAE coin flip);
    that case is detected from the returned ids and resolved by falling
    back to the tie-robust sort-based path.

    Used by the secure-serving example to pick the k best encrypted scores
    without the server learning the values.
    """
    cmp = comparator or compare_fae
    orig = column
    n_rows = column.c0.shape[0]
    k = min(k, n_rows)
    kp = next_pow2(k)                               # power-of-two block
    if pad_value is None:
        pad_value = -(ks.params.max_operand // 2)
    column, n_rows = _pad_to_pow2(ks, column, pad_value, pad_key)
    n_padded = column.c0.shape[0]
    if kp >= n_padded:
        # degenerate: block covers everything — full sort is optimal
        return _topk_via_sort(ks, orig, k, cmp, pad_key)

    c0, c1 = column.c0, column.c1
    perm = jnp.arange(n_padded)
    # phase 1: sort every kp-block descending (flip the ascending flags of
    # the standard network); all blocks ride in the same batched stages
    for lo, hi, asc in _bitonic_pairs(kp):
        glo, ghi, gasc = _block_pairs(n_padded // kp, kp, lo, hi, ~asc)
        c0, c1, perm = _compare_swap(ks, cmp, c0, c1, perm, glo, ghi, gasc)
    # phase 2: tournament of max-merges
    n_live = n_padded
    while n_live > kp:
        blocks = n_live // kp
        j = jnp.arange(blocks // 2)
        i = jnp.arange(kp)
        lo_idx = ((2 * j * kp)[:, None] + i[None, :]).ravel()
        hi_idx = (((2 * j + 1) * kp)[:, None] + (kp - 1 - i)[None, :]).ravel()
        keep_larger = jnp.zeros(lo_idx.shape[0], bool)      # asc=False
        c0, c1, perm = _compare_swap(ks, cmp, c0, c1, perm,
                                     lo_idx, hi_idx, keep_larger)
        # compact surviving (even) blocks to the front
        c0, c1, perm = c0[lo_idx], c1[lo_idx], perm[lo_idx]
        n_live //= 2
        # re-sort each bitonic survivor block descending: log kp merge stages
        stride = kp // 2
        while stride >= 1:
            within = jnp.arange(kp)
            p = within[(within & stride) == 0]               # [kp/2]
            glo, ghi, gasc = _block_pairs(
                n_live // kp, kp, p, p + stride,
                jnp.zeros(p.shape[0], bool))
            c0, c1, perm = _compare_swap(ks, cmp, c0, c1, perm,
                                         glo, ghi, gasc)
            stride //= 2
    top_idx = perm[:k]
    if bool(jnp.any(top_idx >= n_rows)):
        # a real row equal to the sentinel lost a coin-flip tie and a pad
        # row took its slot — rare; the sort path strips by id, not value
        return _topk_via_sort(ks, orig, k, cmp, pad_key)
    return Ciphertext(c0[:k], c1[:k]), top_idx


def _topk_via_sort(ks: KeySet, column: Ciphertext, k: int, cmp: Callable,
                   pad_key: Optional[jax.Array],
                   ) -> Tuple[Ciphertext, jax.Array]:
    """Tie-robust top-k: full ascending sort (id-based sentinel stripping)
    then take the k largest, descending."""
    sorted_ct, perm = encrypted_sort(ks, column, cmp, pad_key=pad_key)
    n = column.c0.shape[0]
    sel = jnp.arange(n - 1, n - 1 - k, -1)
    return _gather_ct(sorted_ct, sel), perm[sel]
