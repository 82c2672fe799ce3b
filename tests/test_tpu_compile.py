"""Compile rehearsals: the programs the chip runs, compiled for a
described TPU v5e at paper-bfv widths with the full-noise gadget key.

Nothing runs here — the TPU compiler, which is installed, compiles for a
chip that is described and not attached, and refuses what the chip
would refuse (layouts, memory, int64 in a Pallas kernel).  The topology
is described inside a module fixture, never at import, so every test
worker collects the same tests and only the one given this file loads
the TPU library.  The persistent compilation cache is off around these
compiles (a TPU executable written here could not be read back).
"""
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encrypt as E
from repro.core import ring as R
from repro.core.keys import KeySet
from repro.core.params import make_params
from repro.db import executor as X
from repro.db.table import INGEST_CHUNK_ROWS
from repro.kernels import ops as KO

# a v5e's HBM (Google Cloud documentation, "TPU v5e": 16 GB per chip)
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _residues(params, *lead):
    """Uniform residues [*lead, K, n] (stand-in key material: compiling
    needs shapes and constants, not a keygen)."""
    rng = np.random.default_rng(0)
    q = np.asarray(params.qs, np.int64)[:, None]
    return jnp.asarray(rng.integers(0, q, lead + (params.num_towers,
                                                  params.n)))


@pytest.fixture(scope="module")
def paper_ks():
    """A paper-bfv gadget KeySet with the right shapes."""
    params = make_params("paper-bfv", mode="gadget")
    E_ = params.num_towers * params.gadget_digits_per_tower
    pk = _residues(params)
    return KeySet(params=params, ring=R.make_ring(params), sk=pk, pk0=pk,
                  pk1=pk, cek=None, cek_gadget=None, cek_gadget_ntt=None,
                  cek_rev=_residues(params, E_), pk_ntt=_residues(params, 2))


def _spec(sharding, *shape, dtype=jnp.int64):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _v5e_lane_budget(params) -> int:
    """`device_lane_budget` as a v5e would resolve it."""
    fit = V5E_HBM_BYTES // KO.EVAL_MEMORY_SHARE // KO.eval_lane_bytes(params)
    return int(min(KO.DEFAULT_LANE_BUDGET, fit))


def test_fused_scan_tile_compiles_within_its_memory_share(paper_ks,
                                                          one_chip):
    """One `fused_eval` tile over the full hg38 column (65,536 padded
    rows), two atoms, at the lane tile a v5e derives: the slice that
    cuts the tile from the column (`executor.scan_tile`), which holds
    one 32-bit plane of the int64 column while splitting it into 32-bit
    words, then the eval program, which takes only the tile."""
    params = paper_ks.params
    K, n = params.num_towers, params.n
    A, W = 2, 65536
    t = KO.lane_tile(W, A, None, default=_v5e_lane_budget(params))
    cut = jax.jit(X.scan_tile, static_argnums=(2,)).lower(
        _spec(one_chip, W, K, n), _spec(one_chip, dtype=jnp.int32),
        t).compile()
    assert cut.memory_analysis().temp_size_in_bytes <= W * K * n * 4
    tile = _spec(one_chip, 1, t, K, n)
    compiled = X.jitted_dedup_eval(paper_ks).lower(
        tile, tile, _spec(one_chip, A), _spec(one_chip, A, 1, K, n),
        _spec(one_chip, A, 1, K, n)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= A * t * KO.eval_lane_bytes(params)
    assert temp <= V5E_HBM_BYTES // KO.EVAL_MEMORY_SHARE


@pytest.mark.parametrize("mode", ["gadget", "paper"])
def test_eval_lane_bytes_bounds_the_compiled_temporaries(paper_ks, one_chip,
                                                         mode):
    """The lane-budget formula is an upper bound on what the compiler
    allocates per lane, in both CEK modes."""
    params = make_params("paper-bfv", mode=mode)
    K, n = params.num_towers, params.n
    ks = dataclasses.replace(
        paper_ks, params=params,
        cek_rev=_residues(params, K * params.gadget_digits_per_tower)
        if mode == "gadget" else _residues(params))
    B = 1024
    fn = jax.jit(lambda a0, a1, b0, b1: X.C.eval_value(
        ks, E.Ciphertext(a0, a1), E.Ciphertext(b0, b1)))
    t0 = time.perf_counter()
    compiled = fn.lower(_spec(one_chip, B, K, n), _spec(one_chip, B, K, n),
                        _spec(one_chip, 1, K, n),
                        _spec(one_chip, 1, K, n)).compile()
    # a compare program holding hundreds of int64 `%` took minutes here
    assert time.perf_counter() - t0 < 60
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= B * KO.eval_lane_bytes(params))


def test_index_probe_eval_compiles(paper_ks, one_chip):
    """The binary-search probe: a few gathered index rows against the
    query's trapdoor lanes."""
    K, n = paper_ks.params.num_towers, paper_ks.params.n
    B = 4
    X.jitted_eval(paper_ks).lower(
        E.Ciphertext(_spec(one_chip, B, K, n), _spec(one_chip, B, K, n)),
        E.Ciphertext(_spec(one_chip, B, K, n),
                     _spec(one_chip, B, K, n))).compile()


def test_ingest_encrypt_chunk_compiles(paper_ks, one_chip):
    """One `Table.from_arrays` chunk: INGEST_CHUNK_ROWS rows encrypted."""
    fn = jax.jit(lambda m, key: E.encrypt(paper_ks, m, key))
    compiled = fn.lower(
        _spec(one_chip, INGEST_CHUNK_ROWS),
        _spec(one_chip, 2, dtype=jnp.uint32)).compile()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < V5E_HBM_BYTES // 4)


def test_sharded_scan_compiles_on_four_chips(paper_ks, topo, one_chip):
    """The fused sharded scan under `jax.shard_map` on a 4-chip mesh:
    one shard per chip, no collective in the program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    K, n = paper_ks.params.num_towers, paper_ks.params.n
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shard",))
    S, A, T = 4, 2, 256
    col = jax.ShapeDtypeStruct((S, 1, T, K, n), jnp.int64,
                               sharding=NamedSharding(mesh, P("shard")))
    rep = jax.ShapeDtypeStruct((A, 1, K, n), jnp.int64,
                               sharding=NamedSharding(mesh, P()))
    sel = jax.ShapeDtypeStruct((A,), jnp.int64,
                               sharding=NamedSharding(mesh, P()))
    fn = KO._shard_eval_fn(paper_ks, mesh, "shard", False, False, 8,
                           5, 4, True)
    compiled = fn.lower(col, col, rep, rep, sel).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text


def test_pallas_kernels_are_refused_for_int64(topo, one_chip):
    """The Pallas eval kernels compute on int64 vectors; the TPU
    compiler refuses that, which is why no chip path reaches them."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1

    def f(x):
        return pl.pallas_call(
            kern, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    with pytest.raises(Exception, match="X64|64"):
        jax.jit(f).lower(_spec(one_chip, 8, 128)).compile()
