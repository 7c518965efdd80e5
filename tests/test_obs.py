"""Unit tests for the observability subsystem (``repro.obs``):
metrics primitives, span tracing, and the export surfaces.

Property-style randomized coverage of the histogram invariants lives in
``test_obs_property.py`` (hypothesis); this module is the deterministic
fast lane that always runs.
"""
import asyncio
import io
import json
import math
import threading

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Tracer,
                       dump_loop, hist_delta, hist_quantile, render_line,
                       write_json)


# ---------------------------------------------------------------- metrics
def test_counter_and_gauge_basics():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge()
    g.set(3.0)
    g.add(-1.5)
    assert g.value == 1.5


def test_histogram_edges_strictly_increasing():
    h = Histogram(lo=1e-6, hi=100.0, buckets_per_decade=8)
    assert all(a < b for a, b in zip(h.edges, h.edges[1:]))
    assert h.edges[0] == 1e-6 and h.edges[-1] == 100.0
    # one counts slot per edge + the overflow bucket
    assert len(h.counts) == len(h.edges) + 1


def test_histogram_bucket_boundaries():
    h = Histogram(lo=1e-3, hi=10.0, buckets_per_decade=4)
    # underflow: everything ≤ lo, including 0
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(1e-3) == 0
    # upper edges are inclusive: a value equal to edges[i] lands in i
    for i, e in enumerate(h.edges):
        assert h.bucket_index(e) == i
    # overflow: everything ≥ hi beyond the last edge
    assert h.bucket_index(11.0) == len(h.edges)


def test_histogram_record_and_quantile_semantics():
    h = Histogram(lo=1e-3, hi=10.0, buckets_per_decade=4)
    for v in (0.0, 0.002, 0.02, 0.2, 2.0, 50.0):
        h.record(v)
    assert h.count == 6
    assert h.sum == pytest.approx(52.222)
    assert h.min == 0.0 and h.max == 50.0
    assert h.mean == pytest.approx(52.222 / 6)
    # q=0 → rank 1 → the underflow bucket reports lo
    assert h.quantile(0.0) == h.edges[0]
    # q=1 → rank 6 → the overflow bucket reports the observed max
    assert h.quantile(1.0) == 50.0
    # every finite estimate is an actual bucket upper edge bounding the
    # order statistic from above, within one bucket
    q50 = h.quantile(0.5)
    assert q50 in h.edges and q50 >= 0.02


def test_histogram_quantile_matches_numpy_rank_oracle():
    """Estimate == upper edge of the bucket holding numpy's
    ``inverted_cdf`` order statistic (same ``ceil(q·n)`` rank)."""
    rng = np.random.default_rng(42)
    vals = 10.0 ** rng.uniform(-5, 1.5, size=500)     # spans the range
    h = Histogram(lo=1e-6, hi=100.0, buckets_per_decade=8)
    for v in vals:
        h.record(float(v))
    for q in (0.01, 0.25, 0.5, 0.9, 0.99):
        oracle = float(np.quantile(vals, q, method="inverted_cdf"))
        est = h.quantile(q)
        assert est == h.edges[h.bucket_index(oracle)]
        # multiplicative one-bucket error bound
        assert oracle <= est <= oracle * 10 ** (1 / 8) * (1 + 1e-9)


def test_histogram_empty_and_bad_quantile():
    h = Histogram()
    assert h.quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_merge_conserves_counts():
    a = Histogram(lo=1e-3, hi=1.0, buckets_per_decade=4)
    b = Histogram(lo=1e-3, hi=1.0, buckets_per_decade=4)
    for v in (0.01, 0.1, 5.0):
        a.record(v)
    for v in (0.0001, 0.02, 0.2):
        b.record(v)
    pre = [x + y for x, y in zip(a.counts, b.counts)]
    a.merge(b)
    assert a.counts == pre
    assert a.count == 6
    assert a.sum == pytest.approx(0.01 + 0.1 + 5.0 + 0.0001 + 0.02 + 0.2)
    assert a.min == 0.0001 and a.max == 5.0


def test_histogram_merge_rejects_mismatched_edges():
    a = Histogram(lo=1e-3, hi=1.0)
    b = Histogram(lo=1e-3, hi=10.0)
    with pytest.raises(ValueError):
        a.merge(b)
    with pytest.raises(ValueError):
        hist_delta(a.snapshot(), b.snapshot())


def test_histogram_snapshot_json_round_trip():
    h = Histogram(lo=1e-4, hi=10.0, buckets_per_decade=4)
    for v in (0.001, 0.05, 0.5, 20.0):
        h.record(v)
    snap = h.snapshot()
    back = Histogram.from_snapshot(json.loads(json.dumps(snap)))
    assert back.snapshot() == snap
    for q in (0.1, 0.5, 0.9):
        assert back.quantile(q) == h.quantile(q)


def test_hist_delta_isolates_a_wave():
    h = Histogram(lo=1e-3, hi=1.0)
    for v in (0.01, 0.02):
        h.record(v)
    before = h.snapshot()
    for v in (0.1, 0.2, 0.4):
        h.record(v)
    wave = hist_delta(h.snapshot(), before)
    assert wave["count"] == 3
    assert wave["sum"] == pytest.approx(0.7)
    assert sum(wave["counts"]) == 3
    # the wave's median comes from the wave, not the cumulative history
    assert hist_quantile(wave, 0.5) >= 0.1


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 2)
    reg.gauge("a.depth").set(7)
    reg.observe("a.lat", 0.01)
    assert reg.counter("a.count") is reg.counter("a.count")
    snap = reg.snapshot()
    assert snap["counters"] == {"a.count": 3}
    assert snap["gauges"] == {"a.depth": 7.0}
    assert snap["histograms"]["a.lat"]["count"] == 1
    assert reg.names() == ["a.count", "a.depth", "a.lat"]
    json.dumps(snap)                     # JSON-serializable end to end


def test_registry_merge_snapshot_fleet_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, k in ((a, 2), (b, 5)):
        reg.inc("req", k)
        reg.gauge("depth").set(k)
        for i in range(k):
            reg.observe("lat", 0.01 * (i + 1))
    a.merge_snapshot(b.snapshot())
    snap = a.snapshot()
    assert snap["counters"]["req"] == 7
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat"]["count"] == 7


def test_gauge_max_mode_merges_as_watermark():
    """Summing is wrong for watermarks: three replicas each 2 entries
    stale is a fleet 2 entries stale, not 6. Max-mode gauges keep the
    worst replica visible through merge_snapshot."""
    merged = MetricsRegistry()
    for stale in (2.0, 0.0, 2.0):
        r = MetricsRegistry()
        r.gauge("fleet.staleness_seq", "max").set(stale)
        r.gauge("depth").set(stale)          # default sum-mode sibling
        merged.merge_snapshot(r.snapshot())
    snap = merged.snapshot()
    assert snap["gauges"]["fleet.staleness_seq"] == 2.0
    assert snap["gauges"]["depth"] == 4.0
    assert snap["gauge_modes"] == {"fleet.staleness_seq": "max"}


def test_gauge_mode_conflict_rejected():
    reg = MetricsRegistry()
    reg.gauge("g", "max")
    assert reg.gauge("g").mode == "max"      # None = whatever exists
    with pytest.raises(ValueError):
        reg.gauge("g", "sum")
    with pytest.raises(ValueError):
        MetricsRegistry().gauge("h", "median")


def test_counter_is_thread_safe_under_contention():
    """The regression the registry exists for: concurrent increments from
    many threads must not lose updates (the old ``stats[k] += 1`` dict
    did, across the event loop + offload worker)."""
    reg = MetricsRegistry()
    n_threads, n_incs = 8, 2000

    def hammer():
        for _ in range(n_incs):
            reg.inc("hot")
            reg.observe("lat", 1e-4)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hot").value == n_threads * n_incs
    assert reg.histogram("lat").count == n_threads * n_incs


# ----------------------------------------------------------------- trace
def test_span_nesting_and_registry_backing():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    with tr.span("outer", who="a") as outer:
        with tr.span("inner") as inner:
            inner.set(rows=3)
        assert inner.parent_id == outer.span_id
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]
    assert evs[0]["parent_id"] == evs[1]["span_id"]
    assert evs[1]["parent_id"] is None
    assert evs[0]["attrs"] == {"rows": 3}
    assert evs[1]["attrs"] == {"who": "a"}
    assert all(e["duration_s"] >= 0 for e in evs)
    assert reg.histogram("outer").count == 1
    assert reg.histogram("inner").count == 1


def test_span_nesting_isolated_across_asyncio_tasks():
    tr = Tracer()

    async def task(name):
        with tr.span(name):
            await asyncio.sleep(0.01)
            with tr.span(name + ".child"):
                await asyncio.sleep(0.01)

    async def main():
        await asyncio.gather(task("a"), task("b"))

    asyncio.run(main())
    by_id = {e["span_id"]: e for e in tr.events()}
    for ev in tr.events():
        if ev["name"].endswith(".child"):
            # each child is parented under ITS OWN task's root span
            assert by_id[ev["parent_id"]]["name"] == ev["name"][:-6]


def test_tracer_event_records_retro_duration():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    tr.event("queue_wait", 0.25, fp="abc")
    (ev,) = tr.events("queue_wait")
    assert ev["duration_s"] == 0.25
    assert ev["attrs"] == {"fp": "abc"}
    snap = reg.histogram("queue_wait").snapshot()
    assert snap["count"] == 1 and snap["sum"] == pytest.approx(0.25)


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(max_events=4)
    for i in range(10):
        tr.event("e", 0.0, i=i)
    evs = tr.events()
    assert len(evs) == 4
    assert [e["attrs"]["i"] for e in evs] == [6, 7, 8, 9]


# ---------------------------------------------------------------- export
def _sample_registry():
    reg = MetricsRegistry()
    reg.inc("engine.requests", 3)
    reg.gauge("engine.queue_depth").set(2)
    for v in (0.001, 0.01, 0.1):
        reg.observe("engine.e2e", v)
    return reg


def test_write_json_round_trips(tmp_path):
    reg = _sample_registry()
    path = tmp_path / "metrics.json"
    write_json(reg.snapshot(), str(path))
    assert json.loads(path.read_text()) == reg.snapshot()


def test_render_line_mentions_everything():
    line = render_line(_sample_registry().snapshot())
    assert line.startswith("stats: ")
    assert "engine.requests=3" in line
    assert "engine.e2e[n=3," in line and "ms]" in line


def test_dump_loop_emits_and_stops():
    reg = _sample_registry()
    seen = []

    async def main():
        await dump_loop(reg, 0.01, emit=seen.append, max_dumps=3)

    asyncio.run(main())
    assert len(seen) == 3
    assert all(s.startswith("stats: ") for s in seen)
