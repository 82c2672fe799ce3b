"""`ShardSpec`: how a sharded table maps logical shards onto devices.

The shard count is a LOGICAL choice (how the rows partition, how many
merge lanes the cross-shard networks get) and is deliberately decoupled
from the physical device count: the same 4-shard table runs 4-way on a
TPU slice, 2-way on a 2-device host, and on a single CPU device — query
answers are identical in all three placements (the shard-invariance
contract tests/test_db_shard.py asserts).

Placement reuses the launch/parallel machinery: `launch.mesh.
make_shard_mesh` builds the 1-D device mesh and `parallel.sharding.
shard_leading` pins `[S, ...]` ciphertext stacks to it.  When the shard
count divides the mesh axis the fused filter launches run under
`shard_map` (`kernels.ops.shard_eval_values`); otherwise execution falls
back to one fused launch on the default device with no semantic change.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True, eq=False)
class ShardSpec:
    """S logical shards + an optional 1-D device mesh to place them on."""
    num_shards: int
    mesh: Optional[Any] = None          # jax.sharding.Mesh with `axis`
    axis: str = "shard"

    def __post_init__(self):
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1: {self.num_shards}")

    @classmethod
    def create(cls, num_shards: int, *, use_mesh: bool = True,
               axis: str = "shard") -> "ShardSpec":
        """Spec over the local devices (the common entry point).

        `use_mesh=False` keeps everything on the default device — useful
        for differential testing of the placement itself.
        """
        mesh = None
        if use_mesh:
            from repro.launch.mesh import make_shard_mesh
            mesh = make_shard_mesh(num_shards, axis=axis)
        return cls(num_shards=num_shards, mesh=mesh, axis=axis)

    # -- placement geometry -------------------------------------------------

    @property
    def mesh_devices(self) -> int:
        """Devices on the shard axis (1 when meshless)."""
        return int(self.mesh.shape[self.axis]) if self.mesh is not None else 1

    @property
    def placeable(self) -> bool:
        """Can a [S, ...] stack split evenly over the mesh axis?"""
        return (self.mesh is not None
                and self.num_shards % self.mesh_devices == 0)

    @property
    def shard_map_ok(self) -> bool:
        """Run fused launches under shard_map (needs >1 device AND even
        placement; a 1-device mesh gains nothing over plain jit)."""
        return self.placeable and self.mesh_devices > 1

    def shard_devices(self) -> Optional[list]:
        """The device holding each shard when stacks split over the mesh
        (None when everything stays on the default device)."""
        if not self.placeable or self.mesh_devices == 1:
            return None
        per = self.num_shards // self.mesh_devices
        devs = list(self.mesh.devices.flat)
        return [devs[s // per] for s in range(self.num_shards)]

    def stack(self, parts: list):
        """[S, ...] array from per-shard arrays, each already on its
        shard's device (`shard_devices`): every device stacks its own
        shards and the global array is assembled in place, so no device
        ever holds more than its share.  Meshless: a plain stack."""
        devs = self.shard_devices()
        if devs is None:
            import jax.numpy as jnp
            return jnp.stack(parts)
        import jax
        import jax.numpy as jnp

        from repro.parallel.sharding import leading_sharding
        per = self.num_shards // self.mesh_devices
        local = [jnp.stack(parts[i:i + per])
                 for i in range(0, self.num_shards, per)]
        shape = (self.num_shards,) + tuple(parts[0].shape)
        return jax.make_array_from_single_device_arrays(
            shape, leading_sharding(self.mesh, len(shape), self.axis), local)

    def place(self, tree):
        """Pin every [S, ...] array leaf's leading dim to the mesh.  A
        no-op when the spec has no usable mesh, so callers never branch."""
        if not self.placeable or self.mesh_devices == 1:
            return tree
        from repro.parallel.sharding import shard_leading
        return shard_leading(self.mesh, tree, self.axis)

    def __repr__(self) -> str:
        return (f"ShardSpec(shards={self.num_shards}, "
                f"devices={self.mesh_devices}, axis={self.axis!r})")
