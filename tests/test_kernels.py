"""Pallas kernels vs pure-jnp oracles: exact equality over shape sweeps.

Modular integer arithmetic admits no tolerance — assert_array_equal, not
allclose.  interpret=True executes the kernel body on CPU.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import compare as C
from repro.core import encrypt as E
from repro.core import ring as R
from repro.core import sampling
from repro.core.keys import keygen
from repro.core.params import PROFILES, Profile, make_params
from repro.kernels import ops, ref

import dataclasses


def _params(n, towers, mode="gadget"):
    prof = dataclasses.replace(PROFILES["test-bfv"], n=n, num_towers=towers,
                               name=f"sweep-{n}-{towers}")
    return make_params(prof, mode=mode)


@pytest.mark.parametrize("n", [64, 256, 1024])
@pytest.mark.parametrize("towers", [1, 2])
@pytest.mark.parametrize("batch", [1, 5, 8])
def test_ntt_kernel_sweep(n, towers, batch):
    params = _params(n, towers)
    ring = R.make_ring(params)
    x = sampling.uniform_poly(params, jax.random.PRNGKey(n + batch), (batch,))
    got = ops.ntt(x, ring)
    want = ref.ntt_br(x, ring, fwd=True)
    assert jnp.array_equal(got, want)
    back = ops.intt(got, ring)
    assert jnp.array_equal(back, x)


@pytest.mark.parametrize("n,towers,batch", [(64, 1, 3), (256, 2, 8),
                                            (1024, 1, 2)])
def test_fused_mul_kernel_sweep(n, towers, batch):
    params = _params(n, towers)
    ring = R.make_ring(params)
    a = sampling.uniform_poly(params, jax.random.PRNGKey(1), (batch,))
    b = sampling.uniform_poly(params, jax.random.PRNGKey(2), (batch,))
    got = ops.negacyclic_mul(a, b, ring)
    want = ref.negacyclic_mul(a, b, ring)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("mode", ["paper", "gadget"])
@pytest.mark.parametrize("batch", [2, 7])
def test_fused_compare_kernel(mode, batch):
    params = make_params("test-bfv", mode=mode)
    ks = keygen(params, jax.random.PRNGKey(42),
                paper_ecek_weight=0 if mode == "paper" else None)
    a = jnp.arange(batch, dtype=jnp.int64) * 3 - 4
    b = jnp.flip(a)
    ct_a = E.encrypt(ks, a, jax.random.PRNGKey(8))
    ct_b = E.encrypt(ks, b, jax.random.PRNGKey(9))
    want = C.compare(ks, ct_a, ct_b)
    got = ops.compare(ks, ct_a, ct_b)
    assert jnp.array_equal(got, want)
    assert jnp.array_equal(got, jnp.sign(a - b).astype(jnp.int32))


def test_kernel_block_padding():
    """Batches not divisible by block_b are padded and truncated."""
    params = _params(64, 1)
    ring = R.make_ring(params)
    for batch in (1, 3, 9, 17):
        x = sampling.uniform_poly(params, jax.random.PRNGKey(batch),
                                  (batch,))
        got = ops.ntt(x, ring, block_b=8)
        assert got.shape == x.shape
        assert jnp.array_equal(got, ref.ntt_br(x, ring, fwd=True))


def test_kernel_eval_matches_core_eval_value(bfv_params, bfv_keys):
    """The fused kernel's coeff0 decode equals core eval_value exactly."""
    from repro.core.compare import eval_value, ct_sub
    from repro.core.gadget import digit_decompose
    from repro.kernels import cmp_eval as CK
    a = jnp.asarray([4, -2], jnp.int64)
    b = jnp.asarray([1, 5], jnp.int64)
    ct_a = E.encrypt(bfv_keys, a, jax.random.PRNGKey(0))
    ct_b = E.encrypt(bfv_keys, b, jax.random.PRNGKey(1))
    want = eval_value(bfv_keys, ct_a, ct_b)
    d = ct_sub(bfv_keys.ring, ct_a, ct_b)
    digits = digit_decompose(bfv_params, d.c1)
    Bb = digits.shape[0]
    Eg = bfv_params.num_towers * bfv_params.gadget_digits_per_tower
    dig = jnp.broadcast_to(
        digits.reshape(Bb, Eg, 1, bfv_params.n),
        (Bb, Eg, bfv_params.num_towers, bfv_params.n))
    coeff0 = CK.eval_coeff0_gadget(
        jnp.pad(d.c0, ((0, 6), (0, 0), (0, 0))),
        jnp.pad(dig, ((0, 6), (0, 0), (0, 0), (0, 0))),
        CK.cek_gadget_to_br(bfv_keys), bfv_keys.ring, bfv_params.scale)
    got = R.crt_centered(bfv_params, coeff0[:2])
    assert jnp.array_equal(got, want)


def test_shard_eval_values_on_one_device_mesh(bfv_keys):
    """`jax.shard_map` as this JAX spells it: the shard-parallel eval on
    a 1-device shard mesh equals the plain eval (the multi-device path
    runs the same call)."""
    from repro.launch.mesh import make_shard_mesh
    ks = bfv_keys
    m = jnp.arange(12, dtype=jnp.int64).reshape(2, 6) % 5
    col = E.encrypt(ks, m, jax.random.PRNGKey(3))           # [S=2, 6]
    b = E.encrypt(ks, jnp.asarray([1, 3], jnp.int64),
                  jax.random.PRNGKey(4))
    bounds = E.Ciphertext(b.c0[:, None], b.c1[:, None])      # [A=2, 1]
    mesh = make_shard_mesh(1)
    cols = E.Ciphertext(col.c0[:, None], col.c1[:, None])    # [S, 1, 6]
    got = ops.shard_eval_values(ks, cols, bounds, mesh=mesh)
    want = C.eval_value(ks, cols, bounds)
    assert got.shape == (2, 2, 6)
    assert jnp.array_equal(got, want)


def test_shard_mesh_never_shrinks_quietly(caplog):
    from repro.launch.mesh import make_shard_mesh
    have = len(jax.devices())
    mesh = make_shard_mesh(have)
    assert len({d.id for d in mesh.devices.flat}) == have
    with caplog.at_level("WARNING", logger="repro.launch.mesh"):
        mesh = make_shard_mesh(2 * have + 1)     # no divisor but 1 fits
    assert mesh.devices.size == 1
    assert f"{2 * have + 1} shards over 1 of {have} devices" in caplog.text
