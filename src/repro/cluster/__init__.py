"""repro.cluster — sharded multi-device RangeReach serving.

The 2DReach forest partitions by tree id (each component's R-tree is an
independent lookup target); :class:`ShardedEngine` serves the partition
over a mesh with ``shard_map`` (replicated pointer lookup, per-shard
Pallas descent, OR-reduce), and :class:`Frontend` micro-batches a
request stream into the power-of-two buckets the engines compile for.

    eng  = ShardedEngine(build_index(g, "2dreach-comp"), n_shards=8)
    ans  = eng.query_batch(us, rects)         # bit-identical to host
    with Frontend(eng, max_batch=256) as fe:  # request-at-a-time surface
        fut = fe.submit(u, rect)
"""

from .frontend import Frontend
from .partition import (
    ForestPartition,
    balanced_assignment,
    partition_forest,
    shard_arenas,
)
from .sharded_engine import ShardedEngine, make_shard_mesh, sharded_engine_for

__all__ = [
    "Frontend",
    "ForestPartition",
    "balanced_assignment",
    "partition_forest",
    "shard_arenas",
    "ShardedEngine",
    "make_shard_mesh",
    "sharded_engine_for",
]
