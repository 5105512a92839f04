from .csr import CSRGraph
from .synthetic import SyntheticSpec, make_benchmark, BENCHMARKS
from .sampling import NeighborSampler, SampledBlocks
from .sage import GraphSAGE, SAGEParams
from .gat import GAT, GATParams
from .distributed import (PartitionedGraph, build_partitioned_graph,
                          make_distributed_forward, make_overlap_forward,
                          make_pallas_attn_agg, make_pallas_mean_agg,
                          make_pallas_split_agg, make_ref_attn_agg,
                          make_ref_mean_agg, make_ref_split_agg)
from .featstore import (FeatureBudgetError, GlobalFeatStore,
                        PartitionFeatStore, build_global_feat_store,
                        build_partition_feat_store, feat_peak_bytes)

__all__ = [
    "CSRGraph", "SyntheticSpec", "make_benchmark", "BENCHMARKS",
    "NeighborSampler", "SampledBlocks", "GraphSAGE", "SAGEParams",
    "GAT", "GATParams",
    "PartitionedGraph", "build_partitioned_graph", "make_distributed_forward",
    "make_overlap_forward", "make_pallas_mean_agg", "make_pallas_split_agg",
    "make_ref_mean_agg", "make_ref_split_agg", "make_pallas_attn_agg",
    "make_ref_attn_agg",
    "FeatureBudgetError", "GlobalFeatStore", "PartitionFeatStore",
    "build_global_feat_store", "build_partition_feat_store",
    "feat_peak_bytes",
]
