"""Distributed runtime: device mesh, sharding rules, collectives.

Replaces the reference's torch.distributed layer (ref:
imaginaire/utils/distributed.py, utils/trainer.py:193-216) with a
jax.sharding Mesh + jit-partitioned train steps. Data parallelism is
expressed as batch sharding over the ``data`` mesh axis; XLA inserts the
gradient all-reduce (the moral equivalent of DDP's bucketed NCCL
all-reduce) during SPMD partitioning, riding ICI within a host/pod slice
and DCN across hosts.
"""

from jax import shard_map

from imaginaire_tpu.parallel.mesh import (
    create_mesh,
    get_mesh,
    mesh_from_config,
    set_mesh,
    init_distributed,
    get_rank,
    get_world_size,
    is_master,
    master_only,
    master_only_print,
)
from imaginaire_tpu.parallel.partition import (
    DEFAULT_RULES,
    PartitionPlan,
    per_device_tree_bytes,
    state_bytes_report,
)
from imaginaire_tpu.parallel.sharding import (
    batch_sharding,
    replicated_sharding,
    shard_batch,
    place_committed_batch,
    data_axis_size,
)

__all__ = [
    "shard_map",
    "create_mesh",
    "get_mesh",
    "mesh_from_config",
    "DEFAULT_RULES",
    "PartitionPlan",
    "per_device_tree_bytes",
    "state_bytes_report",
    "set_mesh",
    "init_distributed",
    "get_rank",
    "get_world_size",
    "is_master",
    "master_only",
    "master_only_print",
    "batch_sharding",
    "replicated_sharding",
    "shard_batch",
    "place_committed_batch",
    "data_axis_size",
]
