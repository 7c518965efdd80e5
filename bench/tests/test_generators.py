"""The generators' copies: a seed gives the same edges, and the
configurations' graphs have the counts their files state."""
import numpy as np
import pytest

from bench import harness, reference


@pytest.mark.parametrize("config", ["livejournal-s18", "graph500-s18"])
def test_same_seed_same_edges(config):
    cfg = harness.load_json("configs", config)
    a = harness.make_graph(cfg, seed=5, rehearse=True)
    b = harness.make_graph(cfg, seed=5, rehearse=True)
    c = harness.make_graph(cfg, seed=6, rehearse=True)
    assert np.array_equal(a.base, b.base) and np.array_equal(a.perm, b.perm)
    # another seed renames the vertices of the same graph
    assert np.array_equal(a.base, c.base)
    assert not np.array_equal(a.perm, c.perm)
    ga = reference.Graph.from_pairs(a.n, a.edges())
    gc = reference.Graph.from_pairs(c.n, c.edges())
    assert ga.m == gc.m
    assert np.array_equal(np.sort(ga.degrees()), np.sort(gc.degrees()))


@pytest.mark.parametrize("config", ["livejournal-s18", "graph500-s18"])
def test_counts_at_configuration_size(config):
    cfg = harness.load_json("configs", config)
    rg = harness.make_graph(cfg, seed=0)
    g = reference.Graph.from_pairs(rg.n, rg.base)
    deg = g.degrees()
    want = cfg["counts"]
    assert (g.m, 2 * g.m, int(deg.max()), int((deg == 0).sum())) == (
        want["m"], want["m2"], want["max_degree"], want["isolated"])


def test_chung_lu_copy_draws_the_programs_graph():
    from repro.core.graph import power_law_graph
    cfg = harness.load_json("configs", "livejournal-s18")
    params = harness.graph_params(cfg, rehearse=True)
    rg = harness.make_graph(cfg, seed=0, rehearse=True)
    g = power_law_graph(**params, seed=cfg["graph_seed"])
    mine = reference.Graph.from_pairs(rg.n, rg.base)
    assert np.array_equal(mine.lo, np.asarray(g.edge_u)[
        np.asarray(g.edge_u) < np.asarray(g.nbrs)])
