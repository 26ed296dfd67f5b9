"""TPU-native parallelism layer.

This package is the TPU seam of the framework (reference SURVEY §1 "key
facts": GPU code in Ray lives in the accelerator plugins, NCCL collective
group, Train's TorchConfig, and NCCL DAG channels — here all of it is
replaced by one coherent JAX/XLA layer):

- :mod:`ray_tpu.parallel.mesh` — device meshes with named axes
  (dp/fsdp/tp/sp/ep/pp), single- and multi-host.
- :mod:`ray_tpu.parallel.sharding` — logical-axis → mesh-axis rules and
  PartitionSpec derivation for parameters and activations.
- :mod:`ray_tpu.parallel.ops` — mesh-aware collective helpers usable inside
  jit (psum/all_gather/ppermute wrappers).

Unlike the reference's `ray.util.collective` (NCCL via cupy,
``python/ray/util/collective/collective_group/nccl_collective_group.py:128``)
where collectives are explicit host-initiated calls, the TPU-idiomatic path
is: build a Mesh, annotate shardings, let XLA insert collectives over ICI/DCN.
The explicit-collective API lives in :mod:`ray_tpu.collective` for parity.
"""

from ray_tpu.parallel.mesh import (
    MeshConfig,
    make_mesh,
    mesh_shape_for,
    local_mesh,
)

# sharding.py imports jax; its names load on first use (PEP 562) so that
# MeshConfig — plain data a jax-free driver puts into a ScalingConfig —
# does not pull jax into that process.
_LAZY = ("ShardingRules", "DEFAULT_RULES", "logical_to_spec",
         "shard_params", "constrain")


def __getattr__(name):
    if name in _LAZY:
        from ray_tpu.parallel import sharding

        value = getattr(sharding, name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "MeshConfig",
    "make_mesh",
    "mesh_shape_for",
    "local_mesh",
    "ShardingRules",
    "DEFAULT_RULES",
    "logical_to_spec",
    "shard_params",
    "constrain",
]
