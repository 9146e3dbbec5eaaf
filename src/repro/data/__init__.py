"""Data substrate: synthetic LBSN graphs, the dataset registry and query
workloads."""

from .lbsn import SPECS, LBSNSpec, dataset_stats, generate_lbsn
from .queries import (
    DEGREE_BUCKETS,
    DEGREE_DEFAULT,
    KNN_DEFAULT_K,
    POLYGON_EDGE_VALUES,
    POLYGON_EDGES_DEFAULT,
    REGION_EXTENT_DEFAULT,
    REGION_EXTENT_VALUES,
    SELECTIVITY_VALUES,
    STREAM_OP_KINDS,
    ZIPF_DEFAULT_S,
    apply_stream_op,
    knn_workload,
    polygon_workload,
    streaming_workload,
    workload,
    zipf_workload,
)
from .registry import dataset_names, get_dataset
