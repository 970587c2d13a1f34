"""Sharded multi-chip serving replicas: gang-scheduled SPMD engines.

A gang replica runs the prefill/decode/verify forwards tensor-sharded over
a mesh (the train-only ``lzy_tpu.parallel`` rules applied to serving) while
presenting the exact ``PagedInferenceEngine`` contract the gateway, streams,
spec, tenancy, and chaos layers already speak. One logical replica, N
devices; one dead host fails over the whole gang.
"""

from lzy_tpu.serving.sharded.engine import (
    GangHostDead,
    NoPartitionRules,
    ShardedPagedInferenceEngine,
)
from lzy_tpu.serving.sharded.partition import (
    SERVE_RULES,
    pool_leaf_sharding,
    serve_mesh_for,
    shard_params,
)

__all__ = [
    "GangHostDead",
    "NoPartitionRules",
    "SERVE_RULES",
    "ShardedPagedInferenceEngine",
    "pool_leaf_sharding",
    "serve_mesh_for",
    "shard_params",
]
