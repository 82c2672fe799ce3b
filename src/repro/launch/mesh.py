"""Production mesh construction (the dry-run contract).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init).

Geometry: TPU v5e-256 pods.  Single pod = (data=16, model=16); two pods =
(pod=2, data=16, model=16).  `pod` composes with `data` for the batch
dimension; weights are never sharded across pods (DESIGN.md §5).
"""
from __future__ import annotations

import logging
import math

import jax

from jax.sharding import AxisType

log = logging.getLogger(__name__)


def _mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "BEFORE importing jax (launch/dryrun.py does this)")
    return _mesh(shape, axes, devices[:need])


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over the real local devices (tests / CPU training)."""
    n = jax.device_count()
    dp = n // model_parallel
    return _mesh((dp, model_parallel), ("data", "model"))


def make_shard_mesh(num_shards: int, *, axis: str = "shard"):
    """1-D device mesh for `repro.db.shard` tables.

    Shard count is LOGICAL (chosen by the table's `ShardSpec`): the mesh
    takes d = the largest divisor of `num_shards` the host can supply,
    so a `[num_shards, ...]`-leading ciphertext stack always places
    evenly (4 shards run 4-way on a four-chip host and on one device of
    a one-device host, with identical answers).  A mesh of fewer devices
    than shards is logged as a warning, never built quietly; a caller
    that needs a given device count checks `mesh.devices`.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    have = jax.devices()
    d = 1
    for cand in range(min(num_shards, len(have)), 0, -1):
        if num_shards % cand == 0:
            d = cand
            break
    if d < num_shards:
        log.warning("shard mesh: %d shards over %d of %d devices",
                    num_shards, d, len(have))
    return _mesh((d,), (axis,), have[:d])


# TPU v5e single-chip peaks (roofline constants; see brief)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link
