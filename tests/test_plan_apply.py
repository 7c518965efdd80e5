"""Incremental SimilarityPlan maintenance vs build-from-scratch.

The invariant: ``plan.apply(g2, touched)`` is **bit-identical** to
``SimilarityPlan.build(g2, plan.hub_tile)`` — every block, routing table,
norm bit — while doing work proportional to the *touched* rows/classes
(asserted via the ``last_apply`` counters). Covered edit classes:

  * layout-stable row re-packs (same degree class, content change);
  * pow2 class migration (a vertex moving between exactly two blocks);
  * hub tile-row splits and merges under the ``HUB_TILE`` rule;
  * class birth (a width with no predecessor block) and death;
  * emptying the graph and repopulating it.

Plus the plan-cache lifetime regression (entries must die with their
graph, not linger until the next miss sweeps them).
"""
import gc

import numpy as np
import pytest

from repro.core import (EdgeDelta, apply_delta, build_index, from_edge_list,
                        hub_ring_graph, power_law_graph, random_graph)
from repro.core import similarity as sim_mod
from repro.core.similarity import SimilarityPlan, plan_for
from repro.core.update import _edit_edge_set

from _plan_oracle import assert_plan_equal

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    hypothesis = None


def edit(g, plan, delta, tag):
    """One maintained step: returns (g2, successor, build reference)."""
    new_lo, new_hi, new_w, touched, _, _ = _edit_edge_set(g, delta)
    g2 = from_edge_list(
        g.n, np.stack([new_lo, new_hi], axis=1)
        if len(new_lo) else np.zeros((0, 2), np.int64), new_w)
    plan2 = plan.apply(g2, touched)
    ref = SimilarityPlan.build(g2, plan.hub_tile)
    assert_plan_equal(plan2, ref, tag)
    return g2, plan2


def test_stable_rows_repack_in_place():
    """A small edit between low-degree vertices: touched rows rewrite,
    every untouched class block is adopted by identity (same device
    array), and the work counter stays proportional to the edit."""
    g = random_graph(120, 6.0, seed=1, weighted=True)
    plan = SimilarityPlan.build(g)
    # endpoints strictly inside their pow2 class (deg+1 keeps the width),
    # so the insert re-packs two rows without migrating anybody
    deg = plan.deg
    inside = [v for v in range(g.n)
              if 2 <= deg[v] and (deg[v] < 8 or deg[v] & (deg[v] - 1))]
    u, v = None, None
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    for a in inside:
        for b in inside:
            if a < b and not np.any((eu == a) & (ev == b)):
                u, v = a, b
                break
        if u is not None:
            break
    g2, plan2 = edit(g, plan, EdgeDelta.make(
        inserts=[(u, v)], weights=[0.7]), "stable")
    stats = plan2.last_apply
    assert stats["built"] == 0
    # two endpoints → at most their own classes re-pack; everything else
    # must be reused *by identity* (no device copy, no host re-pack)
    assert stats["patched"] + stats["remapped"] <= 2
    assert stats["rows_written"] <= 2
    old_by_width = dict(zip(plan.widths, plan.nbr_blocks))
    reused = sum(plan2.nbr_blocks[i] is old_by_width.get(w)
                 for i, w in enumerate(plan2.widths))
    assert reused == stats["reused"] >= len(plan2.widths) - 2


def test_class_migration_moves_between_two_blocks():
    """Growing a vertex across a pow2 boundary must migrate it between
    exactly its two classes (plus its neighbors' row re-packs)."""
    g = random_graph(100, 4.0, seed=2)
    plan = plan_for(g)
    v = 5
    deg0 = int(plan.deg[v])
    w0 = int(plan.widths[plan.vclass[v]])
    targets = [u for u in range(g.n)
               if u != v and not np.any(
                   (np.asarray(g.edge_u) == v) & (np.asarray(g.nbrs) == u))]
    grow = targets[: w0 - deg0 + 1]          # strictly past the class width
    g2, plan2 = edit(g, plan, EdgeDelta.make(
        inserts=[(v, u) for u in grow]), "migrate")
    assert int(plan2.widths[plan2.vclass[v]]) == 2 * w0
    stats = plan2.last_apply
    assert stats["remapped"] + stats["built"] >= 1    # v's new class
    assert stats["rows_written"] < sum(
        b.shape[0] for b in plan2.nbr_blocks)


def test_hub_tile_split_and_merge():
    """With a tiny hub_tile, growing the hub adds tile rows (split) and
    shrinking it removes them (merge) — both bit-identical to build."""
    g = hub_ring_graph(90, 40, seed=3, weighted=True)
    plan = SimilarityPlan.build(g, hub_tile=16)
    assert int(plan.vtiles[0]) == 3                   # ⌈40/16⌉
    spokes = set(np.asarray(g.nbrs)[np.asarray(g.edge_u) == 0].tolist())
    free = [v for v in range(1, g.n) if v not in spokes]
    g2, plan2 = edit(g, plan, EdgeDelta.make(
        inserts=[(0, v) for v in free[:20]]), "split")
    assert int(plan2.vtiles[0]) == 4                  # ⌈60/16⌉ — split
    hub_nbrs = np.asarray(g2.nbrs)[np.asarray(g2.edge_u) == 0]
    g3, plan3 = edit(g2, plan2, EdgeDelta.make(
        deletes=[(0, int(v)) for v in hub_nbrs[:40]]), "merge")
    assert int(plan3.vtiles[0]) < int(plan2.vtiles[0])


def test_class_birth_and_death():
    """An edit that creates a width no block existed for (all members
    touched → packed fresh), then removes it again."""
    g = from_edge_list(40, [(i, (i + 1) % 8) for i in range(8)])
    plan = SimilarityPlan.build(g)
    assert plan.widths == (8,)
    ins = [(20, v) for v in range(21, 21 + 12)]       # degree 12 → width 16
    g2, plan2 = edit(g, plan, EdgeDelta.make(inserts=ins), "birth")
    assert 16 in plan2.widths
    assert plan2.last_apply["built"] == 1
    g3, plan3 = edit(g2, plan2, EdgeDelta.make(deletes=ins), "death")
    assert plan3.widths == (8,)


def test_empty_and_repopulate():
    g = random_graph(24, 3.0, seed=4, weighted=True)
    plan = SimilarityPlan.build(g)
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    g2, plan2 = edit(g, plan, EdgeDelta.make(
        deletes=[(int(u), int(v)) for u, v in zip(eu, ev) if u < v]),
        "empty")
    assert g2.m2 == 0
    g3, plan3 = edit(g2, plan2, EdgeDelta.make(
        inserts=[(0, 1), (1, 2), (5, 9)], weights=[0.2, 0.5, 0.9]),
        "repopulate")
    assert g3.m2 == 6


def test_noop_apply_reuses_every_block():
    g = random_graph(60, 5.0, seed=5)
    plan = SimilarityPlan.build(g)
    plan2 = plan.apply(g, np.zeros(0, np.int64))
    stats = plan2.last_apply
    assert stats["reused"] == stats["classes"]
    assert stats["rows_written"] == 0
    assert all(a is b for a, b in zip(plan2.nbr_blocks, plan.nbr_blocks))
    assert plan2.norms is plan.norms


def test_vertex_count_change_rejected():
    g = random_graph(20, 3.0, seed=6)
    g_bigger = random_graph(21, 3.0, seed=6)
    with pytest.raises(ValueError, match="vertex count"):
        SimilarityPlan.build(g).apply(g_bigger, np.zeros(0, np.int64))


def test_maintained_plan_serves_sigma():
    """The successor plan is a fully functional engine: σ off the
    maintained blocks matches the dense oracle bitwise (unweighted)."""
    from repro.core.similarity import compute_similarities_dense

    g = power_law_graph(100, 2.1, seed=7, hub_degree=30)
    plan = SimilarityPlan.build(g)
    rng = np.random.default_rng(0)
    for step in range(3):
        delta = EdgeDelta.make(
            inserts=rng.integers(0, g.n, size=(4, 2)),
            deletes=[(int(u), int(v)) for u, v in zip(
                *[a[:2] for a in (np.asarray(g.edge_u), np.asarray(g.nbrs))])])
        g, plan = edit(g, plan, delta, f"serve step={step}")
        got = np.asarray(plan.edge_sims(g.edge_u, g.nbrs, g.wgts, "cosine"))
        want = np.asarray(compute_similarities_dense(g, "cosine"))
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# plan-cache lifetime (bugfix regression)
# --------------------------------------------------------------------------
def test_plan_cache_evicts_on_graph_death():
    """A dead graph's O(m + n) device blocks must leave the cache the
    moment the graph is collected — not at the next cache miss."""
    before = sim_mod.plan_cache_size()
    g = random_graph(50, 4.0, seed=8)
    plan_for(g)
    assert sim_mod.plan_cache_size() == before + 1
    del g
    gc.collect()
    assert sim_mod.plan_cache_size() == before


def test_repeated_deltas_do_not_regrow_plan_cache():
    """The resident-update loop: every apply_delta adopts a plan for the
    new graph and the predecessor's entry dies with its graph."""
    g = random_graph(60, 5.0, seed=9)
    idx = build_index(g, "cosine")
    rng = np.random.default_rng(0)
    base = sim_mod.plan_cache_size()
    for k in range(6):
        ins = rng.integers(0, g.n, size=(3, 2))
        idx, g, _ = apply_delta(idx, g, EdgeDelta.make(inserts=ins))
        gc.collect()
        assert sim_mod.plan_cache_size() <= base + 2, \
            f"plan cache regrew at step {k}"
    assert sim_mod.cached_plan(g) is not None          # live graph cached


def test_adopted_plan_is_served_from_cache():
    """apply_delta must seed the cache so the post-edit graph never pays
    an O(m) plan rebuild (the whole point of incremental maintenance)."""
    g = random_graph(60, 5.0, seed=10)
    idx = build_index(g, "cosine")
    idx2, g2, info = apply_delta(idx, g, EdgeDelta.make(inserts=[(0, 30)]))
    maintained = sim_mod.cached_plan(g2)
    assert maintained is not None
    assert maintained.last_apply is not None           # patched, not built
    assert plan_for(g2) is maintained
    assert info.n_plan_rows >= 1
    assert info.n_plan_classes >= 1


# --------------------------------------------------------------------------
# hypothesis property: apply ≡ build across migration / split / merge
# --------------------------------------------------------------------------
if hypothesis is not None:

    @st.composite
    def plan_edit_scripts(draw):
        """(graph, [EdgeDelta ...], hub_tile) biased toward class
        migrations (hub-heavy inserts/deletes) and tile splits/merges
        (hub_tile small enough that the forced hub is multi-tile)."""
        n = draw(st.integers(8, 28))
        m = draw(st.integers(1, 2 * n))
        pairs = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        pairs = [(u, v) for u, v in pairs if u != v] or [(0, 1)]
        if draw(st.booleans()):                        # force a hub at 0
            pairs += [(0, v) for v in range(1, n)]
        weighted = draw(st.booleans())
        w = (draw(st.lists(st.floats(0.10000000149011612, 1.0,
                                     allow_nan=False, width=32),
                           min_size=len(pairs), max_size=len(pairs)))
             if weighted else None)
        g = from_edge_list(n, np.asarray(pairs, np.int64),
                           np.asarray(w, np.float32) if w else None)
        hub_tile = draw(st.sampled_from([8, 16, 2048]))
        steps = []
        for _ in range(draw(st.integers(1, 3))):
            k_ins = draw(st.integers(0, 5))
            k_del = draw(st.integers(0, 5))
            ins = draw(st.lists(
                st.tuples(st.integers(0, n - 1), st.integers(0, n - 1),
                          st.floats(0.1, 1.0, allow_nan=False)),
                min_size=k_ins, max_size=k_ins))
            if draw(st.booleans()):                    # pile onto the hub
                ins += [(0, draw(st.integers(1, n - 1)), 1.0)]
            dels = draw(st.lists(
                st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
                min_size=k_del, max_size=k_del))
            steps.append((ins, dels))
        return g, steps, hub_tile

    @settings(max_examples=20, deadline=None)
    @given(plan_edit_scripts())
    def test_hypothesis_plan_apply_equals_build(case):
        g, steps, hub_tile = case
        plan = SimilarityPlan.build(g, hub_tile)
        for i, (ins, dels) in enumerate(steps):
            # bias deletions toward edges that actually exist
            eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
            canon = [(int(u), int(v)) for u, v in zip(eu, ev) if u < v]
            real_dels = list(dels)
            if canon and dels:
                real_dels += [canon[(u * 7 + v) % len(canon)]
                              for u, v in dels[:2]]
            delta = EdgeDelta.make(
                inserts=[(u, v) for u, v, _ in ins],
                weights=[w for _, _, w in ins],
                deletes=real_dels)
            g, plan = edit(g, plan, delta, f"step {i}")
