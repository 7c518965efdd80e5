"""Degree-bucketed similarity engine vs the dense oracle.

Precision contract (asserted here):

* **unweighted** graphs (both measures): every intermediate — shared
  counts, degrees, norms² — is a small integer, exact in float32 under any
  reduction order, so the bucketed engine is **bit-identical** to
  ``compute_similarities_dense`` whatever the degree classes, hub tiling,
  or chunking do to the reduction tree;
* **weighted** cosine: float sums are reduction-order-sensitive, so
  engine-vs-oracle agreement is to float32 resolution (≤ ~deg·ulp), while
  the engine itself stays bit-deterministic (subset ≡ full slice, chunked
  ≡ unchunked) — the property the incremental-update oracle relies on.
"""
import jax
import numpy as np
import pytest

from repro.core import (
    EdgeDelta,
    apply_delta,
    build_index,
    compute_similarities,
    compute_similarities_dense,
    compute_similarities_densepad,
    edge_similarities_subset,
    from_edge_list,
    hub_ring_graph,
    plan_for,
    power_law_graph,
    random_graph,
    triangle_counts,
)
from repro.core import similarity as sim_mod
from repro.core.similarity import SimilarityPlan, densepad_operand_bytes

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    hypothesis = None


def assert_matches_oracle(g, measure):
    got = np.asarray(compute_similarities(g, measure))
    want = np.asarray(compute_similarities_dense(g, measure))
    if np.all(np.asarray(g.wgts) == 1.0):
        np.testing.assert_array_equal(got, want)       # bitwise, unweighted
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


CASES = [
    (random_graph(40, 5.0, seed=1), "cosine"),
    (random_graph(40, 5.0, seed=1), "jaccard"),
    (random_graph(64, 7.0, seed=2, weighted=True), "cosine"),
    (hub_ring_graph(60, 45), "cosine"),
    (hub_ring_graph(60, 45), "jaccard"),
    (power_law_graph(150, 2.1, seed=3, hub_degree=64), "jaccard"),
    (power_law_graph(150, 2.1, seed=4, weighted=True, hub_degree=64),
     "cosine"),
]


@pytest.mark.parametrize("g,measure", CASES)
def test_bucketed_matches_dense_oracle(g, measure):
    assert_matches_oracle(g, measure)


def test_forced_hub_tiling_exact():
    """A deg ≫ median hub forced through multi-tile rows (tiny hub_tile)
    stays bit-identical to the oracle AND to the untiled plan (unweighted:
    tile-order partial sums are integer-exact)."""
    g = hub_ring_graph(80, 60)
    assert int(np.asarray(g.degrees()).max()) == 60      # hub dominates
    assert int(np.median(np.asarray(g.degrees()))) <= 3
    tiled = plan_for(g, hub_tile=16)
    assert int(tiled.vtiles.max()) > 1                   # splitting engaged
    s_tiled = np.asarray(tiled.edge_sims(g.edge_u, g.nbrs, g.wgts, "cosine"))
    s_flat = np.asarray(compute_similarities(g, "cosine"))
    s_oracle = np.asarray(compute_similarities_dense(g, "cosine"))
    np.testing.assert_array_equal(s_tiled, s_oracle)
    np.testing.assert_array_equal(s_flat, s_oracle)


def test_subset_bit_identical_to_full_pass():
    """The frontier-recompute path: any edge subset must reproduce the
    full pass bit-for-bit (this is what lets apply_delta carry σ)."""
    g = power_law_graph(120, 2.1, seed=5, weighted=True, hub_degree=40)
    full = np.asarray(compute_similarities(g, "cosine"))
    eu, ev, w = np.asarray(g.edge_u), np.asarray(g.nbrs), np.asarray(g.wgts)
    rng = np.random.default_rng(0)
    idx = rng.choice(g.m2, size=g.m2 // 3, replace=False)
    sub = np.asarray(edge_similarities_subset(
        g, eu[idx], ev[idx], w[idx], "cosine"))
    np.testing.assert_array_equal(sub, full[idx])


def test_chunked_bit_identical():
    g = power_law_graph(100, 2.1, seed=6, weighted=True, hub_degree=30)
    a = np.asarray(compute_similarities(g, "cosine", chunk=64))
    b = np.asarray(compute_similarities(g, "cosine", chunk=1 << 16))
    np.testing.assert_array_equal(a, b)


def _isolated_vertices_graph():
    """Two triangles and a path among 40 vertices: most have no edge."""
    return from_edge_list(40, np.array([(3, 7), (7, 9), (3, 9), (20, 21),
                                        (21, 22), (20, 22), (30, 31),
                                        (31, 35)]))


TWINNED_CASES = {
    # hubs wider than HUB_TILE: their rows split into tile rows
    "hub_tiles": (hub_ring_graph(2600, 2300), "cosine", 1 << 16),
    "weighted": (random_graph(64, 7.0, seed=2, weighted=True), "cosine",
                 1 << 16),
    "jaccard": (power_law_graph(150, 2.1, seed=3, hub_degree=64), "jaccard",
                1 << 16),
    "isolated": (_isolated_vertices_graph(), "cosine", 1 << 16),
    "empty": (from_edge_list(5, np.zeros((0, 2), np.int64)), "cosine",
              1 << 16),
    "multi_chunk": (power_law_graph(400, 2.1, seed=13, weighted=True,
                                    hub_degree=150), "cosine", 32),
}


@pytest.mark.parametrize("case", list(TWINNED_CASES))
def test_twinned_pass_matches_every_half_edge_computed(case):
    """σ computed on the upper half-edges and given to each twin is, bit
    for bit, σ computed on all m2 half-edges."""
    g, measure, chunk = TWINNED_CASES[case]
    if case == "hub_tiles":
        assert int(np.asarray(g.degrees()).max()) > sim_mod.HUB_TILE
    plan = plan_for(g)
    want = np.asarray(plan.edge_sims(g.edge_u, g.nbrs, g.wgts, measure,
                                     chunk))
    got = np.asarray(plan.half_edge_sims(g, None, measure, chunk))
    assert got.shape == want.shape == (g.m2,)
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(compute_similarities(g, measure, chunk)).view(np.uint8),
        want.view(np.uint8))
    if case == "hub_tiles":
        assert int(plan.vtiles.max()) > 1               # tile rows engaged


def test_twinned_pass_on_a_twin_closed_subset_keeps_its_order():
    """A subset closed under twins (both halves of random undirected
    edges) gets σ in its own order, the bits of every half computed."""
    g = power_law_graph(200, 2.1, seed=9, weighted=True, hub_degree=80)
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    rng = np.random.default_rng(4)
    pick = rng.random(g.m2) < 0.4
    key = np.minimum(eu, ev) * g.n + np.maximum(eu, ev)
    sel = np.flatnonzero(np.isin(key, key[pick & (eu < ev)]))
    assert 0 < len(sel) < g.m2
    plan = plan_for(g)
    want = np.asarray(plan.edge_sims(eu[sel], ev[sel],
                                     np.asarray(g.wgts)[sel], "cosine"))
    got = np.asarray(plan.half_edge_sims(g, sel, "cosine"))
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def test_twinned_pass_refuses_a_set_not_closed_under_twins():
    g = random_graph(60, 5.0, seed=3)
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    with pytest.raises(ValueError, match="closed under twins"):
        plan_for(g).half_edge_sims(g, np.flatnonzero(eu < ev))
    with pytest.raises(ValueError, match="closed under twins"):
        plan_for(g).half_edge_sims(g, np.flatnonzero(eu > ev)[:-1])


def test_traced_build_counts_each_sigma_once_and_untraced_counts_nothing(
        monkeypatch):
    from repro.obs import MetricsRegistry, Tracer

    g = power_law_graph(300, 2.1, seed=15, hub_degree=100)
    tr = Tracer(MetricsRegistry())
    build_index(g, tracer=tr)
    c = tr.registry.snapshot()["counters"]
    assert c["similarity.sigma_computed"] == g.m
    assert c["similarity.sigma_twinned"] == g.m
    # untraced: the process-default registry, which counts the lanes of
    # every build, gains no σ counter, and no tracer is asked to count
    from repro.backend.policy import default_policy

    def no_count(*a, **k):
        raise AssertionError("an untraced build counted")
    monkeypatch.setattr(Tracer, "inc", no_count)
    before = default_policy().registry.snapshot()["counters"]
    build_index(power_law_graph(300, 2.1, seed=15, hub_degree=100))
    after = default_policy().registry.snapshot()["counters"]
    assert {k: v for k, v in after.items() if k.startswith("similarity.")} \
        == {k: v for k, v in before.items() if k.startswith("similarity.")}


QUEUED_CASES = [
    (hub_ring_graph(300, 200), "cosine"),
    (power_law_graph(400, 2.1, seed=12, hub_degree=150), "jaccard"),
    (power_law_graph(400, 2.1, seed=13, weighted=True, hub_degree=150),
     "cosine"),
    (power_law_graph(400, 2.1, seed=14, hub_degree=150), "_count"),
]


@pytest.mark.parametrize("g,measure", QUEUED_CASES)
def test_queued_chunks_match_one_chunk_per_group(g, measure):
    """Many chunks per group, queued on the device, give the bits of one
    chunk per group."""
    from repro.obs import MetricsRegistry, Tracer

    plan = plan_for(g)
    args = (g.edge_u, g.nbrs, g.wgts, measure)
    tr = Tracer(MetricsRegistry())
    one = plan.edge_sims(*args, tracer=tr)
    assert {e["attrs"]["chunks"] for e in tr.events("similarity.group")} \
        == {1}
    many = plan.edge_sims(*args, chunk=32)
    assert max(e for *_, e in plan.last_group_shapes) > 8 * 32
    assert isinstance(many, jax.Array) and many.dtype == one.dtype
    a, b = np.asarray(many), np.asarray(one)
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def test_traced_pass_counts_queued_chunks_and_one_wait_per_group():
    from repro.obs import MetricsRegistry, Tracer

    g = hub_ring_graph(300, 200)
    plan = plan_for(g)
    tr = Tracer(MetricsRegistry())
    plan.edge_sims(g.edge_u, g.nbrs, g.wgts, chunk=32, tracer=tr)
    snap = tr.registry.snapshot()
    chunks = snap["counters"]["similarity.chunks"]
    assert chunks == sum(e["attrs"]["chunks"]
                         for e in tr.events("similarity.group"))
    assert chunks > sim_mod.INFLIGHT_CHUNKS      # the window engages
    assert 0 <= snap["counters"].get("similarity.chunks_queued", 0) < chunks
    waits = snap["histograms"]["similarity.device_wait"]
    assert waits["count"] == plan.last_groups


def test_pass_makes_no_host_copy(monkeypatch):
    """σ stays on the device: nothing in the pass copies a device array to
    the host (the routing reads host inputs only)."""
    g = power_law_graph(400, 2.1, seed=15, weighted=True, hub_degree=150)
    plan = plan_for(g)
    eu, ev, ew = (np.asarray(a) for a in (g.edge_u, g.nbrs, g.wgts))
    want = np.asarray(plan.edge_sims(eu, ev, ew, chunk=32))
    host_copy = np.asarray

    def guarded(a, *args, **kw):
        if isinstance(a, jax.Array):
            raise AssertionError("the similarity pass copied to the host")
        return host_copy(a, *args, **kw)
    monkeypatch.setattr(np, "asarray", guarded)
    got = plan.edge_sims(eu, ev, ew, chunk=32)
    monkeypatch.undo()
    assert isinstance(got, jax.Array)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                  want.view(np.uint8))


def test_triangle_counts_exact():
    g = power_law_graph(90, 2.1, seed=7, hub_degree=40)
    import jax.numpy as jnp
    a = np.asarray(jnp.zeros((g.n, g.n)).at[g.edge_u, g.nbrs].set(1.0))
    ref = (a @ a)[np.asarray(g.edge_u), np.asarray(g.nbrs)].astype(np.int32)
    np.testing.assert_array_equal(np.asarray(triangle_counts(g)), ref)


def test_operand_memory_beats_dense_padding():
    """On a hub graph the bucketed operands are ≥10× smaller than the
    O(n·Δ) dense-padded matrices (the acceptance bar; on real power-law
    graphs the gap grows with n·Δ/m)."""
    g = hub_ring_graph(2048, 512)
    plan = plan_for(g)
    dense_bytes = densepad_operand_bytes(g)
    assert dense_bytes >= 10 * plan.operand_bytes(), (
        dense_bytes, plan.operand_bytes())
    # and the bucketed layout stays O(m + n): blocks ≤ 2·m2 + floor·n slots
    slots = sum(int(np.prod(b.shape)) for b in plan.nbr_blocks)
    assert slots <= 2 * g.m2 + sim_mod.BUCKET_FLOOR * g.n + 2 * len(
        plan.widths) * sim_mod.HUB_TILE


def test_densepad_legacy_path_agrees():
    g = power_law_graph(120, 2.1, seed=8, weighted=True, hub_degree=48)
    a = np.asarray(compute_similarities(g, "cosine"))
    b = np.asarray(compute_similarities_densepad(g, "cosine"))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_jit_cache_hoisted_across_apply_delta_batches():
    """Repeated apply_delta batches at the same pow2 subset size must reuse
    one compiled kernel per degree-class pair: the bucketed chunk kernel's
    jit cache stops growing after the first batch warms it."""
    g = random_graph(64, 6.0, seed=10)
    idx = build_index(g, "cosine")
    # absent edges to insert and then remove again: every batch is the same
    # pow2 subset size and the same degree-class pairs, so after one warm
    # insert+delete cycle no new kernel shape may appear
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    present = {(int(u), int(v)) for u, v in zip(eu, ev)}
    absent = [(u, v) for u in range(g.n) for v in range(u + 1, g.n)
              if (u, v) not in present][:4]
    ins = EdgeDelta.make(inserts=absent)
    dels = EdgeDelta.make(deletes=absent)

    idx, g, _ = apply_delta(idx, g, ins)          # warm the caches
    idx, g, _ = apply_delta(idx, g, dels)
    progs = (sim_mod._bucket_sims_chunk, sim_mod._chunk_edges,
             sim_mod._place_chunk)
    warm = [p._cache_size() for p in progs]
    for _ in range(3):
        idx, g, info = apply_delta(idx, g, ins)
        assert info.n_frontier > 0
        idx, g, info = apply_delta(idx, g, dels)
        assert info.n_frontier > 0
    assert [p._cache_size() for p in progs] == warm


def test_plan_cache_reuses_per_graph_object():
    g = random_graph(30, 4.0, seed=11)
    assert plan_for(g) is plan_for(g)
    p = SimilarityPlan.build(g)
    assert p is not plan_for(g)


def test_isolated_vertices_and_empty_graph():
    g = from_edge_list(12, [(0, 1), (1, 2)])       # vertices 3..11 isolated
    assert_matches_oracle(g, "cosine")
    assert_matches_oracle(g, "jaccard")
    g0 = from_edge_list(6, np.zeros((0, 2), np.int64))
    assert compute_similarities(g0).shape == (0,)
    assert triangle_counts(g0).shape == (0,)


def test_pallas_probe_matches_engine_stats():
    """The Pallas bucket-probe kernel (interpret mode) reproduces the jnp
    engine's shared dot/count on real plan-gathered rows, including a
    tiled hub target (the streaming k-axis)."""
    from repro.kernels import ops as kops

    g = hub_ring_graph(48, 30, weighted=True, seed=2)
    plan = plan_for(g, hub_tile=16)
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    pu, pv, _ = plan.route(eu.astype(np.int64), ev.astype(np.int64))
    # gather full-width rows host-side (tiles concatenated, pad id = n)
    def gather(v):
        c = int(plan.vclass[v])
        blk_n = np.asarray(plan.nbr_blocks[c])
        blk_w = np.asarray(plan.wgt_blocks[c])
        r0, t = int(plan.vrow[v]), int(plan.vtiles[v])
        return blk_n[r0:r0 + t].reshape(-1), blk_w[r0:r0 + t].reshape(-1)

    wmax = max(len(gather(v)[0]) for v in range(g.n))
    rp = np.full((g.m2, wmax), g.n, np.int32)
    wp = np.zeros((g.m2, wmax), np.float32)
    rt = np.full((g.m2, wmax), g.n, np.int32)
    wt = np.zeros((g.m2, wmax), np.float32)
    for e in range(g.m2):
        a, b = gather(pu[e])
        rp[e, :len(a)], wp[e, :len(a)] = a, b
        a, b = gather(pv[e])
        rt[e, :len(a)], wt[e, :len(a)] = a, b
    dot, cnt = kops.bucket_probe_stats(
        jax.numpy.asarray(rp), jax.numpy.asarray(wp),
        jax.numpy.asarray(rt), jax.numpy.asarray(wt), g.n, be=32, bt=16)
    # numpy reference: sorted-set intersection per edge
    w_lut = {}
    for u, v, w in zip(eu, ev, np.asarray(g.wgts)):
        w_lut[(int(u), int(v))] = float(w)
    for e in range(g.m2):
        u, v = int(pu[e]), int(pv[e])
        nu = rp[e][rp[e] < g.n]
        nv = rt[e][rt[e] < g.n]
        shared = np.intersect1d(nu, nv)
        want_cnt = len(shared)
        want_dot = sum(w_lut[(u, int(x))] * w_lut[(v, int(x))]
                       for x in shared)
        assert int(cnt[e]) == want_cnt
        np.testing.assert_allclose(float(dot[e]), want_dot, rtol=1e-5,
                                   atol=1e-6)


# --------------------------------------------------------------------------
# hypothesis property: bucketed ≡ dense oracle
# --------------------------------------------------------------------------
if hypothesis is not None:

    @st.composite
    def graphs(draw):
        n = draw(st.integers(6, 24))
        m = draw(st.integers(0, 2 * n))
        pairs = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        pairs = [(u, v) for u, v in pairs if u != v]
        if draw(st.booleans()):                        # force a hub at 0
            pairs += [(0, v) for v in range(1, n)]
        weighted = draw(st.booleans())
        if not pairs:
            pairs = [(0, 1)]
        w = (draw(st.lists(st.floats(0.10000000149011612, 1.0,
                                     allow_nan=False, width=32),
                           min_size=len(pairs), max_size=len(pairs)))
             if weighted else None)
        g = from_edge_list(n, np.asarray(pairs, np.int64),
                           np.asarray(w, np.float32) if w else None)
        measure = draw(st.sampled_from(
            ["cosine"] if weighted else ["cosine", "jaccard"]))
        return g, measure

    @settings(max_examples=40, deadline=None)
    @given(graphs())
    def test_hypothesis_bucketed_vs_dense_oracle(case):
        g, measure = case
        assert_matches_oracle(g, measure)
        # engine self-consistency is always bitwise, weighted or not
        a = np.asarray(compute_similarities(g, measure, chunk=32))
        b = np.asarray(compute_similarities(g, measure))
        np.testing.assert_array_equal(a, b)
