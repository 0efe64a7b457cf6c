from nerrf_tpu.parallel.mesh import (
    MeshConfig,
    make_mesh,
    batch_sharding,
    param_sharding,
    init_distributed,
)
from nerrf_tpu.parallel.train import (
    make_sharded_train_step,
    shard_batch,
    init_sharded_state,
    make_stream_train_step,
    stream_shardings,
)
from nerrf_tpu.parallel.ring import ring_self_attention

__all__ = [
    "MeshConfig",
    "make_mesh",
    "batch_sharding",
    "param_sharding",
    "init_distributed",
    "make_sharded_train_step",
    "shard_batch",
    "init_sharded_state",
    "make_stream_train_step",
    "stream_shardings",
    "ring_self_attention",
]
