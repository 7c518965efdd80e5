"""Approximate-first ingest against the plain reference.

``LiveIndexService.register_approximate`` (simhash sketches, the ``hamming``
comparison, the §6.3 exact subset through the similarity plan, the index
orders) is checked against :mod:`repro.core.scan_ref`, which sketches with
float64 sums over the same float32 projections and counts Hamming
distances by Python integers. The graphs carry hubs above k, so that
some edges are sketched:

* the exact subset's σ equals the reference's bit for bit;
* every sketched half-edge's h equals the reference's (σ̂ read back to h
  through the table ``max(0, cos(π·h/k))``);
* NO and CO equal the reference orders of the served σ̂.

And the tracing of the pass: with no tracer nothing is timed and the bits
are the same; with one, the ``lsh.*`` spans nest under
``index.approx_build`` and the counters add up to m2.
"""
import jax
import numpy as np
import pytest

from repro.core import ApproxParams
from repro.core import lsh as lsh_mod
from repro.core import similarity as sim_mod
from repro.core.approx import ApproxIndexBuilder
from repro.core.graph import from_edge_list
from repro.core.scan_ref import index_orders_ref, simhash_similarities_ref
from repro.obs import MetricsRegistry, Tracer
from repro.serve import EngineConfig, LiveIndexService

ORDER_FIELDS = ("offsets_c", "no_nbrs", "no_sims", "no_self", "cdeg",
                "co_vertex", "co_theta", "co_offsets")


def hub_graph(n=300, avg_degree=6, hubs=12, hub_degree=80, seed=0):
    """G(n, p) with ``hubs`` vertices joined to ``hub_degree`` others and
    to each other: the hubs' closed degree is far above 64, everyone
    else's below 32, so exactly the hub–hub edges are sketched."""
    rng = np.random.default_rng(seed)
    e = [rng.integers(0, n, size=(n * avg_degree // 2, 2))]
    hs = rng.choice(n, hubs, replace=False)
    for h in hs:
        e.append(np.stack([np.full(hub_degree, h),
                           rng.choice(n, hub_degree, replace=False)], 1))
    e.append(np.array([(a, b) for a in hs for b in hs if a < b]))
    e = np.concatenate(e)
    return from_edge_list(n, e[e[:, 0] != e[:, 1]])


def h_of(sims, samples):
    """h read back from σ̂: the index of the nearest entry of the table
    max(0, cos(π·h/k)), h ≤ k/2 (every clipped σ̂ reads k/2)."""
    h = np.arange(samples // 2 + 1)
    table = np.maximum(0.0, np.cos(np.pi * h / samples)).astype(np.float32)
    return np.abs(np.asarray(sims, np.float64)[:, None]
                  - table[None, :]).argmin(axis=1)


def sketched_mask(g, samples):
    cdeg = np.diff(np.asarray(g.offsets)) + 1
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    return (cdeg[eu] > samples) & (cdeg[ev] > samples)


@pytest.mark.parametrize("seed,samples", [(0, 32), (1, 64)])
def test_served_approx_index_matches_the_plain_reference(
        tmp_path, seed, samples):
    g = hub_graph(seed=seed)
    params = ApproxParams(method="simhash", samples=samples, seed=seed + 5)
    svc = LiveIndexService(str(tmp_path), config=EngineConfig(max_batch=8))
    svc.register_approximate("web", g, params=params)
    idx = svc.index("web")
    assert svc.provenance("web").is_approx
    served = np.asarray(idx.edge_sims)
    want = simhash_similarities_ref(g, samples,
                                    jax.random.PRNGKey(params.seed))
    sk = sketched_mask(g, samples)
    assert 0 < sk.sum() < g.m2
    np.testing.assert_array_equal(served[~sk].view(np.uint32),
                                  want[~sk].view(np.uint32))
    np.testing.assert_array_equal(h_of(served[sk], samples),
                                  h_of(want[sk], samples))
    assert len(np.unique(h_of(served[sk], samples))) > 3   # not constant
    ref = index_orders_ref(g, served)
    for field in ORDER_FIELDS:
        got = np.asarray(getattr(idx, field))
        np.testing.assert_array_equal(got, ref[field].astype(got.dtype),
                                      err_msg=field)


def test_untraced_approx_build_times_nothing_and_builds_the_same_bits(
        monkeypatch):
    builder = ApproxIndexBuilder(params=ApproxParams(samples=32, seed=3))
    traced, _ = builder.build(hub_graph(seed=2),
                              tracer=Tracer(MetricsRegistry()))

    def no_timing():
        raise AssertionError("the untraced approximate pass took a timing")
    monkeypatch.setattr(lsh_mod.time, "perf_counter", no_timing)
    monkeypatch.setattr(sim_mod.time, "perf_counter", no_timing)
    plain, _ = builder.build(hub_graph(seed=2))       # fresh plan
    for field in ORDER_FIELDS + ("edge_sims",):
        a, b = np.asarray(getattr(plain, field)), np.asarray(
            getattr(traced, field))
        assert a.dtype == b.dtype and np.array_equal(
            a.view(np.uint8), b.view(np.uint8)), field


@pytest.mark.parametrize("heuristic", [True, False])
def test_approx_spans_nest_and_counters_add_up_to_m2(heuristic):
    g = hub_graph(seed=4)
    tr = Tracer(MetricsRegistry())
    ApproxIndexBuilder(params=ApproxParams(
        samples=32, degree_heuristic=heuristic)).build(g, tracer=tr)
    (top,) = tr.events("index.approx_build")
    stages = ["lsh.sketch", "lsh.compare", "lsh.merge"]
    if heuristic:
        stages.insert(2, "lsh.exact_subset")
    for name in stages + ["index.build"]:
        (ev,) = tr.events(name)
        assert ev["parent_id"] == top["span_id"], name
    (build,) = tr.events("index.build")
    for name in ("index.orders", "index.co_offsets"):
        (ev,) = tr.events(name)
        assert ev["parent_id"] == build["span_id"], name
    snap = tr.registry.snapshot()
    c, h = snap["counters"], snap["histograms"]
    exact = int((~sketched_mask(g, 32)).sum()) if heuristic else 0
    assert c["lsh.exact_edges"] == exact
    assert c["lsh.sketched_edges"] + c["lsh.exact_edges"] == g.m2
    assert h["lsh.device_wait"]["count"] == len(stages)
    assert h["lsh.device_wait"]["sum"] <= sum(h[s]["sum"] for s in stages)
    if heuristic:
        # σ is computed on one half of each twin pair of the subset
        (sub,) = tr.events("lsh.exact_subset")
        assert sub["attrs"]["edges"] == exact // 2
        groups = tr.events("similarity.group")
        assert groups and all(e["parent_id"] == sub["span_id"]
                              for e in groups)
        assert sum(e["attrs"]["edges"] for e in groups) == exact // 2
        assert c["similarity.sigma_computed"] == exact // 2
        assert c["similarity.sigma_twinned"] == exact // 2
    else:
        assert "similarity.group" not in h


@pytest.mark.parametrize("weighted", [False, True])
def test_served_exact_subset_has_the_bits_of_every_half_computed(
        tmp_path, weighted):
    """The exact subset is closed under twins and its σ computed once per
    edge: the served σ̂ there equals ``edge_sims`` over both halves of
    the subset, bit for bit, and the two counters still add up to m2."""
    g = hub_graph(seed=7)
    if weighted:
        eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
        up = eu < ev
        w = np.random.default_rng(7).uniform(0.1, 1.0, up.sum())
        g = from_edge_list(g.n, np.stack([eu[up], ev[up]], 1),
                           w.astype(np.float32))
    samples = 32
    svc = LiveIndexService(str(tmp_path), config=EngineConfig(max_batch=8))
    svc.register_approximate("web", g, params=ApproxParams(
        method="simhash", samples=samples, seed=2))
    served = np.asarray(svc.index("web").edge_sims)
    exact = np.flatnonzero(~sketched_mask(g, samples))
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    key = np.minimum(eu, ev).astype(np.int64) * g.n + np.maximum(eu, ev)
    up, down = exact[eu[exact] < ev[exact]], exact[eu[exact] > ev[exact]]
    assert 0 < len(up) == len(down)
    np.testing.assert_array_equal(np.sort(key[up]), np.sort(key[down]))
    want = np.asarray(jax.numpy.clip(sim_mod.plan_for(g).edge_sims(
        eu[exact], ev[exact], np.asarray(g.wgts)[exact]), 0.0, 1.0))
    np.testing.assert_array_equal(served[exact].view(np.uint8),
                                  want.view(np.uint8))
    c = svc.engine.registry.snapshot()["counters"]
    assert c["lsh.exact_edges"] == len(exact)
    assert c["lsh.sketched_edges"] + c["lsh.exact_edges"] == g.m2
    assert c["similarity.sigma_computed"] == c["similarity.sigma_twinned"] \
        == len(exact) // 2
