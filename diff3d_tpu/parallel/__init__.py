from jax import shard_map

from diff3d_tpu.parallel.mesh import (MeshEnv, batch_sharding, make_mesh,
                                      param_sharding, replicated_sharding,
                                      tp_param_sharding)
from diff3d_tpu.parallel.multihost import maybe_initialize_distributed
from diff3d_tpu.parallel.ring_attention import ring_sdpa, ulysses_sdpa

__all__ = [
    "MeshEnv", "make_mesh", "batch_sharding", "param_sharding",
    "replicated_sharding", "tp_param_sharding",
    "maybe_initialize_distributed", "ring_sdpa", "ulysses_sdpa",
    "shard_map",
]
