"""SCAN engine — the paper's contribution as a composable JAX module."""
from repro.core.graph import (
    CSRGraph,
    from_edge_list,
    graph_from_dense,
    hub_ring_graph,
    power_law_graph,
    random_graph,
    to_dense,
)
from repro.core.similarity import (
    SimilarityPlan,
    compute_similarities,
    compute_similarities_dense,
    edge_similarities_subset,
    plan_for,
    triangle_counts,
)
from repro.core.index import ScanIndex, build_index, get_cores
from repro.core.query import ClusterResult, query, query_batch, hubs_outliers
from repro.core.local import (SeedBatchResult, SeedResult, query_seeds,
                              query_seeds_device)
from repro.core.lsh import (
    approximate_similarities,
    simhash_sketches,
    simhash_edge_similarity,
    minhash_sketches,
    minhash_edge_similarity,
    kpartition_sketches,
    kpartition_edge_similarity,
)
from repro.core.approx import (
    EXACT_PROVENANCE,
    ApproxIndexBuilder,
    ApproxParams,
    IndexProvenance,
    build_approx_index,
)
from repro.core.update import EdgeDelta, UpdateInfo, apply_delta
from repro.core.quality import (adjusted_rand_index, core_precision_recall,
                                modularity)
from repro.core.connectivity import (
    connected_components,
)
from repro.core.distributed import (
    ShardedQueryPlan,
    force_host_devices,
    query_batch_sharded,
    query_mesh,
)
