"""The program's spans on the profiler's clock, the traced index build,
and the query's round counter.

* a ``Tracer`` span is also a profiler annotation: it shows on the host
  plane of a ``jax.profiler`` trace, by name and nested;
* ``build_index(..., tracer=t)`` traces the ingest path (one
  ``similarity.group`` per kernel group, chunks counted, device waits
  inside the pass); with no tracer it times nothing and builds the same
  bits;
* ``query_batch`` reports each lane's own propagation rounds, and the
  engine's ``query.rounds`` / ``query.lane_rounds`` counters add each
  call's slowest lane and the sum over its slots.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.backend.padding import pow2_bucket, pow2ceil
from repro.core import (build_index, plan_for, power_law_graph, query,
                        query_batch, random_graph)
from repro.core import similarity as sim_mod
from repro.obs import MetricsRegistry, Tracer

MUS = np.asarray([2, 3, 4, 5, 2, 7], np.int32)
EPSS = np.asarray([0.2, 0.3, 0.5, 0.4, 0.9, 0.25], np.float32)


def _host_events(trace_dir):
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def test_span_lands_nested_on_the_profiler_host_plane(tmp_path):
    tr = Tracer(MetricsRegistry())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("test.outer", edges=12):
            with tr.span("test.inner"):
                jax.block_until_ready(jax.numpy.ones(8).sum())
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(tmp_path)
    (o0, o1, o_stats), = evs["test.outer"]
    (i0, i1, _), = evs["test.inner"]
    assert o0 <= i0 < i1 <= o1
    assert o_stats.get("edges") == 12       # attributes ride as stats
    # the ring and the histograms still see both spans
    assert [e["name"] for e in tr.events()] == ["test.inner", "test.outer"]
    assert tr.registry.snapshot()["histograms"]["test.outer"]["count"] == 1


def test_tracer_inc_counts_into_its_registry():
    tr = Tracer(MetricsRegistry())
    tr.inc("a.chunks", 3)
    tr.inc("a.chunks")
    Tracer().inc("a.chunks", 5)             # no registry: a no-op
    assert tr.registry.counter("a.chunks").value == 4


def _chunks(pe, te, lane, edges, pol):
    """The chunk count :meth:`SimilarityPlan.edge_sims` gives a group."""
    cap = max(sim_mod.CHUNK_ELEMS // max(pe + te, 1), 1)
    cap = 1 << (cap.bit_length() - 1)
    if lane == sim_mod.LANE_COMPILED:
        cap = max(cap, sim_mod.KERNEL_LANE_EDGES)
    csize = min(pow2_bucket(edges), max(min(1 << 16, cap), 1))
    if lane == sim_mod.LANE_INTERPRET:
        csize = min(csize, pow2ceil(pol.profile.probe_interpret_chunk))
    return -(-edges // csize)


def test_traced_build_records_groups_chunks_and_waits():
    from repro.backend.policy import default_policy

    g = power_law_graph(600, avg_degree=8.0, seed=3)
    tr = Tracer(MetricsRegistry())
    build_index(g, tracer=tr)
    shapes = plan_for(g).last_group_shapes
    groups = tr.events("similarity.group")
    assert [(e["attrs"]["pe"], e["attrs"]["te"], e["attrs"]["lane"],
             e["attrs"]["edges"]) for e in groups] == list(shapes)
    want = sum(_chunks(*s, default_policy()) for s in shapes)
    assert [e["attrs"]["chunks"] for e in groups] == [
        _chunks(*s, default_policy()) for s in shapes]
    snap = tr.registry.snapshot()
    assert snap["counters"]["similarity.chunks"] == want
    h = snap["histograms"]
    assert h["similarity.device_wait"]["count"] == len(shapes)
    assert h["similarity.device_wait"]["sum"] <= h["similarity.pass"]["sum"]
    for name in ("index.build", "similarity.pass", "similarity.plan",
                 "similarity.route", "index.orders", "index.co_offsets"):
        assert h[name]["count"] == 1, name
    # nesting: the pass and the orders inside the build, groups in the pass
    (build,), (sp,) = tr.events("index.build"), tr.events("similarity.pass")
    (orders,) = tr.events("index.orders")
    assert sp["parent_id"] == build["span_id"]
    assert orders["parent_id"] == build["span_id"]
    assert all(e["parent_id"] == sp["span_id"] for e in groups)


def test_untraced_build_times_nothing_and_builds_the_same_bits(monkeypatch):
    g = random_graph(300, 7.0, seed=21, weighted=True)
    traced = build_index(g, tracer=Tracer(MetricsRegistry()))

    def no_timing():
        raise AssertionError("the untraced similarity pass took a timing")
    monkeypatch.setattr(sim_mod.time, "perf_counter", no_timing)
    g2 = random_graph(300, 7.0, seed=21, weighted=True)   # fresh plan
    plain = build_index(g2)
    for field in ("offsets_c", "no_nbrs", "no_sims", "no_self", "co_offsets",
                  "co_vertex", "co_theta", "cdeg", "edge_sims"):
        a, b = np.asarray(getattr(plain, field)), np.asarray(
            getattr(traced, field))
        assert a.dtype == b.dtype and np.array_equal(
            a.view(np.uint8), b.view(np.uint8)), field


@pytest.mark.parametrize("seed", [4, 5])
def test_batched_rounds_are_each_lanes_own(seed):
    g = power_law_graph(400, avg_degree=8.0, seed=seed)
    idx = build_index(g)
    res = query_batch(idx, g, MUS, EPSS)
    rounds = np.asarray(res.rounds)
    assert rounds.shape == (len(MUS),) and rounds.dtype == np.int32
    for k, (mu, eps) in enumerate(zip(MUS, EPSS)):
        one = query(idx, g, mu, eps)
        assert int(one.rounds) == rounds[k]
        np.testing.assert_array_equal(np.asarray(one.labels),
                                      np.asarray(res.labels)[k])
    assert rounds.min() >= 1                # the last round changes nothing
    assert rounds.max() > rounds.min()      # lanes converge apart


def test_engine_round_counters_follow_the_rule(monkeypatch):
    import repro.serve.engine as engine_mod
    from repro.serve import EngineConfig, MicroBatchEngine

    g = power_law_graph(300, avg_degree=8.0, seed=8)
    idx = build_index(g)
    calls = []
    orig = engine_mod.query_batch

    def recording(index, g, mus, epss):
        res = orig(index, g, mus, epss)
        calls.append(np.asarray(res.rounds))
        return res
    monkeypatch.setattr(engine_mod, "query_batch", recording)

    async def main():
        async with MicroBatchEngine(idx, g, config=EngineConfig(
                max_batch=8)) as eng:
            for mu, eps in ((2, 0.3), (3, 0.5), (4, 0.25)):
                await asyncio.gather(*(eng.query(mu + d, eps)
                                       for d in range(3)))
            return eng.registry.snapshot()["counters"]

    counters = asyncio.run(main())
    assert calls and counters["engine.device_queries"] == len(calls)
    assert counters["query.rounds"] == sum(int(r.max()) for r in calls)
    assert counters["query.lane_rounds"] == sum(int(r.sum()) for r in calls)
    assert all(len(r) == 8 for r in calls)
