"""SPMD parallelism: device mesh construction and sharding helpers."""

from raft_tpu.parallel.mesh import (  # noqa: F401
    abstract_replicated,
    make_mesh,
    batch_sharding,
    data_parallel_kernels,
    kernel_mesh,
    make_batch_sharder,
    mesh_shape,
    place_replicated,
    replicated_sharding,
    shard_batch,
    spatial_batch_sharding,
)
