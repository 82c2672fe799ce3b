"""Ring arithmetic + NTT correctness (the kernels' mathematical ground)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # collection must survive without hypothesis
    HAVE_HYPOTHESIS = False

from repro.core import ring as R
from repro.core import sampling
from repro.core.params import (make_params, ntt_primes, negacyclic_root,
                               is_prime, PROFILES)


@pytest.fixture(scope="module")
def ring(bfv_params):
    return R.make_ring(bfv_params)


def test_ntt_primes_properties():
    for n in (256, 1024, 4096):
        for q in ntt_primes(n, 2):
            assert is_prime(q)
            assert q % (2 * n) == 1
            assert q < 2**31
            psi = negacyclic_root(q, n)
            assert pow(psi, n, q) == q - 1
            assert pow(psi, 2 * n, q) == 1


def test_ntt_roundtrip(bfv_params, ring):
    a = sampling.uniform_poly(bfv_params, jax.random.PRNGKey(0), (3,))
    assert jnp.array_equal(R.intt(ring, R.ntt(ring, a)), a)


def test_ntt_mul_matches_naive(bfv_params, ring):
    a = sampling.uniform_poly(bfv_params, jax.random.PRNGKey(1))
    b = sampling.uniform_poly(bfv_params, jax.random.PRNGKey(2))
    fast = R.negacyclic_mul(ring, a, b)
    slow = R.naive_negacyclic_mul(ring, a, b)
    assert jnp.array_equal(fast, slow)


def test_negacyclic_wraparound(bfv_params, ring):
    """x^(n-1) * x = x^n = -1 in R_q."""
    n, K = bfv_params.n, bfv_params.num_towers
    a = jnp.zeros((K, n), jnp.int64).at[:, n - 1].set(1)
    b = jnp.zeros((K, n), jnp.int64).at[:, 1].set(1)
    out = R.negacyclic_mul(ring, a, b)
    qs = np.asarray(bfv_params.qs)
    expect = jnp.zeros((K, n), jnp.int64).at[:, 0].set(
        jnp.asarray(qs - 1))
    assert jnp.array_equal(out, expect)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(-2**40, 2**40))
    def test_crt_centered_roundtrip(v):
        params = make_params("test-ckks", mode="gadget")   # 2 towers
        res = jnp.asarray([[v % q for q in params.qs]], jnp.int64)
        got = int(R.crt_centered(params, res)[0])
        assert got == v, (got, v)
else:
    def test_crt_centered_roundtrip():
        pytest.importorskip("hypothesis")


def test_const_poly_embedding(bfv_params):
    vals = jnp.asarray([0, 1, -1, 1000], jnp.int64)
    p = R.const_poly(bfv_params, vals)
    assert p.shape == (4, bfv_params.num_towers, bfv_params.n)
    got = R.crt_centered(bfv_params, p[..., :, 0])
    assert jnp.array_equal(got, vals)
    assert int(jnp.sum(jnp.abs(p[..., 1:]))) == 0


def test_ring_add_sub_inverse(bfv_params, ring):
    a = sampling.uniform_poly(bfv_params, jax.random.PRNGKey(3))
    b = sampling.uniform_poly(bfv_params, jax.random.PRNGKey(4))
    assert jnp.array_equal(R.sub(ring, R.add(ring, a, b), b), a)


def test_all_profiles_constructible():
    for name in PROFILES:
        p = make_params(name)
        assert p.max_operand > 0, name
        assert p.tau > 0


@pytest.mark.parametrize("profile", ["test-bfv", "paper-bfv"])
def test_division_free_reduction_matches_mod(profile):
    """Barrett `reduce`, `reduce_signed` and the add/sub/mul helpers
    give exactly what int64 `%` gives, at the edges of their ranges."""
    qs = make_params(profile).qs
    mus = R.barrett_mu(qs)
    rng = np.random.default_rng(0)
    for q, mu in zip(qs, mus):
        x = np.concatenate([
            rng.integers(0, 1 << 62, 4096, dtype=np.int64),
            np.array([0, 1, q - 1, q, q + 1, 4 * q, (1 << 62) - 1,
                      (q - 1) * (q - 1), ((1 << 62) // q) * q - 1],
                     np.int64)])
        assert np.array_equal(np.asarray(R.reduce(jnp.asarray(x), q,
                                                  int(mu))), x % q)
        s = np.concatenate([x, -x]) >> 1
        assert np.array_equal(
            np.asarray(R.reduce_signed(jnp.asarray(s), q, int(mu))), s % q)
        a = rng.integers(0, q, 4096, dtype=np.int64)
        b = np.concatenate([rng.integers(0, q, 4094, dtype=np.int64),
                            [0, q - 1]])
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        assert np.array_equal(np.asarray(R.mulmod(ja, jb, q, int(mu))),
                              (a * b) % q)
        assert np.array_equal(np.asarray(R.addmod(ja, jb, q)), (a + b) % q)
        assert np.array_equal(np.asarray(R.submod(ja, jb, q)), (a - b) % q)


def test_barrett_mu_rejects_moduli_outside_its_range():
    with pytest.raises(ValueError):
        R.barrett_mu([(1 << 30) - 35])


@pytest.mark.parametrize("profile", ["test-bfv", "paper-bfv"])
def test_crt_centered_matches_python_crt(profile):
    """Garner CRT decode == the big-integer CRT, centered."""
    params = make_params(profile)
    qs, Q = params.qs, params.Q
    rng = np.random.default_rng(1)
    vals = [int(v) for v in rng.integers(-(Q // 2), Q // 2, 512,
                                         dtype=np.int64)]
    vals += [Q // 2, -(Q // 2) + 1, 0, 1, -1]
    res = np.asarray([[v % q for q in qs] for v in vals], np.int64)
    got = np.asarray(R.crt_centered(params, jnp.asarray(res)))
    assert got.tolist() == vals


def test_coeff0_reversal_gives_product_coefficient_zero(bfv_params, ring):
    """sum_i a_i r_i == coefficient 0 of the negacyclic product a * b."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    a = sampling.uniform_poly(bfv_params, k1, (3,))
    b = sampling.uniform_poly(bfv_params, k2)
    r = R.coeff0_reversal(ring, b)
    want = R.negacyclic_mul(ring, a, b)[..., 0]
    got = R.reduce(jnp.sum(R.pointwise_mul(ring, a, r), axis=-1),
                   ring.q_arr[:, 0], ring.mu_arr[:, 0])
    assert jnp.array_equal(got, want)
