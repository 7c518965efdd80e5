"""The plain reference: triangles by hand, and agreement with the program
on small graphs (σ bit for bit, the index orders, every query answer)."""
import numpy as np
import pytest

from bench import harness, reference


def test_triangles_by_hand():
    # a 4-clique on 0..3 plus a pendant 3-4: every clique edge closes two
    # triangles, the pendant none
    pairs = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3), (3, 4), (4, 3)]
    g = reference.Graph.from_pairs(5, np.array(pairs)).with_triangles()
    got = dict(zip(zip(g.lo.tolist(), g.hi.tolist()), g.tri.tolist()))
    assert got == {(0, 1): 2, (0, 2): 2, (0, 3): 2, (1, 2): 2, (1, 3): 2,
                   (2, 3): 2, (3, 4): 0}


def test_sigma_by_hand():
    # path 0-1-2: |N̄0 ∩ N̄1| = 2, |N̄0| = 2, |N̄1| = 3
    g = reference.Graph.from_pairs(3, np.array([(0, 1), (1, 2)]))
    offsets, eu, nb, tri = g.with_triangles().csr()
    s = reference.sigma(eu, nb, tri, offsets)
    want = np.float32(2) / (np.sqrt(np.float32(2)) * np.sqrt(np.float32(3)))
    assert np.all(s == want)


@pytest.mark.parametrize("config", ["livejournal-s18", "graph500-s18"])
def test_agrees_with_the_program(config):
    from repro.core.graph import from_edge_list
    from repro.core.index import build_index
    from repro.core.query import query_batch

    cfg = harness.load_json("configs", config)
    rg = harness.make_graph(cfg, seed=123, rehearse=True)
    edges = rg.edges()
    g = from_edge_list(rg.n, edges)
    idx = build_index(g)
    ref = harness.RefIndex.build(rg.reference().relabel(rg.perm))
    assert harness.compare_index(harness.host_index(idx),
                                 harness.host_graph(g), ref) == {
        "graph_bad": 0, "sigma_bad": 0, "order_bad": 0}
    mus = np.array([2, 3, 5, 8, 4], np.int32)
    epss = np.array([0.2, 0.3, 0.5, 0.4, 0.35], np.float32)
    res = query_batch(idx, g, mus, epss)
    answers = harness.Clusterings(ref)
    for i in range(len(mus)):
        one = type("A", (), {"labels": np.asarray(res.labels[i]),
                             "is_core": np.asarray(res.is_core[i])})
        assert harness.global_bad(one, answers(int(mus[i]),
                                               float(epss[i]))) == 0
