"""Device meshes and sharded aggregation.

The TPU answer to the reference's scaling story (reference:
rust/xaynet-server's single-threaded in-memory `Aggregation`): HBM-resident
accumulators sharded over the model axis of a `jax.sharding.Mesh`, with
zero-collective elementwise kernels.
"""

from .aggregator import ShardedAggregator
from .mesh import MODEL_AXIS, make_mesh, shard_slices
from .shards import ShardPlan
from .streaming import StreamingAggregator

__all__ = [
    "ShardedAggregator",
    "ShardPlan",
    "StreamingAggregator",
    "MODEL_AXIS",
    "make_mesh",
    "shard_slices",
]
