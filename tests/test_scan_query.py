"""Cluster queries vs the sequential SCAN oracle (paper §4.2-4.3)."""
import numpy as np
import pytest

from repro.core import (
    build_index,
    compute_similarities,
    from_edge_list,
    get_cores,
    hubs_outliers,
    query,
    random_graph,
)
from repro.core.scan_ref import scan_ref

GRAPHS = [
    (random_graph(60, 5.0, seed=11), "cosine"),
    (random_graph(60, 5.0, seed=11), "jaccard"),
    (random_graph(90, 7.0, seed=12, weighted=True), "cosine"),
    (random_graph(45, 3.0, seed=13, planted_clusters=4), "jaccard"),
]
PARAMS = [(2, 0.3), (2, 0.7), (3, 0.5), (5, 0.2), (5, 0.6), (4, 0.9)]


@pytest.mark.parametrize("g,measure", GRAPHS)
def test_query_matches_oracle(g, measure):
    sims = compute_similarities(g, measure)
    idx = build_index(g, measure, sims=sims)
    for mu, eps in PARAMS:
        res = query(idx, g, mu, eps)
        ref = scan_ref(g, mu, eps, measure, sims=np.asarray(sims))
        np.testing.assert_array_equal(np.asarray(res.is_core), ref["is_core"])
        np.testing.assert_array_equal(np.asarray(res.labels), ref["labels"])
        hub, outl = hubs_outliers(g, res.labels)
        np.testing.assert_array_equal(np.asarray(hub), ref["is_hub"])
        np.testing.assert_array_equal(np.asarray(outl), ref["is_outlier"])


def test_paper_figure1_clustering():
    """Paper Fig. 1: (μ=3, ε=.6) → clusters {1,2,3,4} and {6,7,8,11},
    vertex 5 a hub."""
    edges = [(1, 2), (1, 3), (1, 4), (2, 3), (2, 4), (3, 4), (4, 5), (5, 6),
             (6, 7), (6, 8), (7, 8), (7, 11), (8, 11), (7, 9), (8, 10)]
    g = from_edge_list(11, [(u - 1, v - 1) for u, v in edges])
    idx = build_index(g, "cosine")
    res = query(idx, g, 3, 0.6)
    lab = np.asarray(res.labels)
    assert len({lab[0], lab[1], lab[2], lab[3]}) == 1 and lab[0] >= 0
    assert len({lab[5], lab[6], lab[7], lab[10]}) == 1 and lab[5] >= 0
    assert lab[0] != lab[5]
    assert lab[4] == -1
    hub, _ = hubs_outliers(g, res.labels)
    assert bool(hub[4])


def test_core_mask_via_direct_threshold():
    """get_cores (CO-prefix path) ≡ direct θ(v,μ) ≥ ε check."""
    g = random_graph(70, 6.0, seed=14)
    sims = compute_similarities(g, "cosine")
    idx = build_index(g, "cosine", sims=sims)
    for mu in (2, 3, 7):
        for eps in (0.1, 0.5, 0.8):
            a = np.asarray(get_cores(idx, mu, eps))
            theta = np.asarray(idx.core_threshold(mu))
            b = theta >= np.float32(eps)
            np.testing.assert_array_equal(a, b)


def test_query_monotonicity():
    """Raising ε or μ never grows the core set (SCAN definition)."""
    g = random_graph(80, 6.0, seed=15)
    idx = build_index(g, "cosine")
    prev = None
    for eps in (0.2, 0.4, 0.6, 0.8):
        cores = np.asarray(get_cores(idx, 3, eps))
        if prev is not None:
            assert np.all(prev | ~cores)   # cores ⊆ prev
        prev = cores
    prev = None
    for mu in (2, 3, 5, 9):
        cores = np.asarray(get_cores(idx, mu, 0.4))
        if prev is not None:
            assert np.all(prev | ~cores)
        prev = cores


def test_empty_and_degenerate():
    g = from_edge_list(4, [(0, 1)])
    idx = build_index(g, "cosine")
    res = query(idx, g, 2, 0.1)
    ref = scan_ref(g, 2, 0.1, "cosine")
    np.testing.assert_array_equal(np.asarray(res.labels), ref["labels"])
    # μ beyond max degree → no cores
    res = query(idx, g, 10, 0.1)
    assert int(res.n_clusters) == 0
    assert np.all(np.asarray(res.labels) == -1)


@pytest.mark.parametrize("g", [GRAPHS[0][0], GRAPHS[2][0],
                               random_graph(40, 0.4, seed=14)])
def test_row_reduce_and_twin_slots_match_numpy(g):
    """The query's scatter-free pieces: per-row min/max over CSR rows and
    the reverse slot of every half-edge, vs plain numpy."""
    import jax.numpy as jnp

    from repro.core.connectivity import row_layout, row_reduce
    from repro.core.query import twin_slots

    off = np.asarray(g.offsets)
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    max_row = int(np.diff(off).max(initial=0))
    rows = row_layout(g.offsets, g.edge_u, max_row)
    vals = np.random.default_rng(0).integers(0, 1000, g.m2).astype(np.int32)
    got_min = np.asarray(row_reduce(jnp.asarray(vals), rows, jnp.minimum,
                                    jnp.int32(10 ** 6)))
    got_max = np.asarray(row_reduce(jnp.asarray(vals), rows, jnp.maximum,
                                    jnp.int32(-1)))
    for v in range(g.n):
        row = vals[off[v]:off[v + 1]]
        assert got_min[v] == (row.min() if len(row) else 10 ** 6)
        assert got_max[v] == (row.max() if len(row) else -1)
    twin = np.asarray(twin_slots(g, max_row))
    np.testing.assert_array_equal(twin, np.lexsort((eu, ev)))
    np.testing.assert_array_equal(eu[twin], ev)
    np.testing.assert_array_equal(ev[twin], eu)
