"""Serve-subsystem tests: index persistence round-trip, vmapped sweeps,
the LRU result cache, and the async micro-batching engine."""
import asyncio

import numpy as np

from repro.core import (build_index, compute_similarities, query,
                        query_batch, random_graph)
from repro.core.scan_ref import scan_ref
from repro.serve import (EngineConfig, IndexStore, MicroBatchEngine,
                         ResultCache, grid_sweep, index_fingerprint,
                         quantize_eps, sweep, sweep_stats)


def _graph_and_index(n=120, deg=8.0, seed=0):
    g = random_graph(n, deg, seed=seed)
    sims = compute_similarities(g, "cosine")
    return g, build_index(g, "cosine", sims=sims), sims


# --------------------------------------------------------------------------
# persistence
# --------------------------------------------------------------------------
def test_store_roundtrip_preserves_everything(tmp_path):
    g, idx, _ = _graph_and_index()
    store = IndexStore(str(tmp_path))
    store.save(idx, g)
    idx2, g2, fp = store.load()

    for f in ("offsets_c", "no_nbrs", "no_sims", "no_self", "co_offsets",
              "co_vertex", "co_theta", "cdeg", "edge_sims"):
        np.testing.assert_array_equal(np.asarray(getattr(idx, f)),
                                      np.asarray(getattr(idx2, f)), err_msg=f)
    for f in ("offsets", "nbrs", "wgts", "edge_u"):
        np.testing.assert_array_equal(np.asarray(getattr(g, f)),
                                      np.asarray(getattr(g2, f)), err_msg=f)
    assert (idx2.n, idx2.m2c, idx2.max_cdeg) == (idx.n, idx.m2c, idx.max_cdeg)
    assert (g2.n, g2.m2) == (g.n, g.m2)
    assert fp == index_fingerprint(idx, g)


def test_restored_index_queries_match_oracle(tmp_path):
    g, idx, sims = _graph_and_index(n=80, deg=6.0, seed=3)
    IndexStore(str(tmp_path)).save(idx, g)
    idx2, g2, _ = IndexStore(str(tmp_path)).load()
    for mu, eps in ((2, 0.3), (3, 0.5), (4, 0.7)):
        res = query(idx2, g2, mu, eps)
        ref = scan_ref(g, mu, eps, "cosine", sims=np.asarray(sims))
        np.testing.assert_array_equal(np.asarray(res.is_core), ref["is_core"])
        np.testing.assert_array_equal(np.asarray(res.labels), ref["labels"])


def test_store_versioning_and_latest(tmp_path):
    g, idx, _ = _graph_and_index(n=40, deg=4.0)
    store = IndexStore(str(tmp_path), keep=2)
    store.save(idx, g)
    store.save(idx, g)
    assert store.latest_version() == 1
    idx2, g2, _ = store.load(version=0)
    assert g2.n == g.n
    # non-monotone explicit versions are rejected (they'd be GC'd on commit)
    try:
        store.save(idx, g, version=0)
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError")


def test_fingerprint_tracks_content():
    g, idx, _ = _graph_and_index(n=60, deg=6.0, seed=1)
    g_b, idx_b, _ = _graph_and_index(n=60, deg=6.0, seed=1)
    assert index_fingerprint(idx, g) == index_fingerprint(idx_b, g_b)
    g_c, idx_c, _ = _graph_and_index(n=60, deg=6.0, seed=2)
    assert index_fingerprint(idx, g) != index_fingerprint(idx_c, g_c)


# --------------------------------------------------------------------------
# vmapped sweeps
# --------------------------------------------------------------------------
def test_query_batch_matches_sequential_queries():
    """Acceptance criterion: a vmapped sweep over ≥ 16 (μ, ε) settings is
    identical to sequential single queries."""
    g, idx, _ = _graph_and_index(n=150, deg=10.0, seed=5)
    mus = np.asarray([2, 3, 4, 5] * 5, np.int32)
    epss = np.linspace(0.05, 0.95, 20).astype(np.float32)
    assert len(mus) >= 16
    batched = query_batch(idx, g, mus, epss)
    for i, (mu, eps) in enumerate(zip(mus, epss)):
        one = query(idx, g, int(mu), float(eps))
        np.testing.assert_array_equal(np.asarray(batched.labels[i]),
                                      np.asarray(one.labels))
        np.testing.assert_array_equal(np.asarray(batched.is_core[i]),
                                      np.asarray(one.is_core))
        assert int(batched.n_clusters[i]) == int(one.n_clusters)


def test_grid_sweep_covers_cartesian_product():
    g, idx, _ = _graph_and_index(n=60, deg=6.0)
    res = grid_sweep(idx, g, [2, 3], [0.2, 0.5, 0.8])
    assert len(res) == 6
    assert res.labels.shape == (6, g.n)
    # μ-major ordering
    np.testing.assert_array_equal(res.mus, [2, 2, 2, 3, 3, 3])
    np.testing.assert_allclose(res.epss, [0.2, 0.5, 0.8] * 2, rtol=1e-6)
    one = query(idx, g, 3, 0.5)
    np.testing.assert_array_equal(res.result(4).labels, np.asarray(one.labels))


def test_sweep_stats_rows():
    g, idx, _ = _graph_and_index(n=60, deg=8.0)
    rows = sweep_stats(idx, g, [2, 3], [0.1, 0.3])
    assert len(rows) == 4
    for r in rows:
        assert 0.0 <= r["coverage"] <= 1.0
        assert r["n_clusters"] <= max(r["n_cores"], 1)
        assert -1.0 <= r["modularity"] <= 1.0
    # cores are monotone non-increasing in ε at fixed μ
    by_mu = {(r["mu"], round(r["eps"], 3)): r["n_cores"] for r in rows}
    assert by_mu[(2, 0.3)] <= by_mu[(2, 0.1)]


def test_sweep_rejects_mismatched_shapes():
    g, idx, _ = _graph_and_index(n=30, deg=4.0)
    try:
        sweep(idx, g, [2, 3], [0.5])
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError")


# --------------------------------------------------------------------------
# result cache
# --------------------------------------------------------------------------
def test_cache_lru_eviction_and_stats():
    c = ResultCache(capacity=2)
    c.put("fp", 2, 0.5, "a")
    c.put("fp", 3, 0.5, "b")
    assert c.get("fp", 2, 0.5) == "a"      # 2 is now most-recent
    c.put("fp", 4, 0.5, "c")               # evicts 3
    assert c.get("fp", 3, 0.5) is None
    assert c.get("fp", 2, 0.5) == "a"
    st = c.stats()
    assert st["evictions"] == 1 and st["hits"] == 2 and st["misses"] == 1


def test_cache_eps_quantization_aliases_near_identical():
    c = ResultCache(capacity=8, eps_quantum=1e-4)
    c.put("fp", 2, 0.6, "x")
    assert c.get("fp", 2, 0.60000002) == "x"
    assert c.get("fp", 2, 0.6002) is None
    assert quantize_eps(0.60004999) == 0.6
    assert quantize_eps(0.6001) == 0.6001


def test_eps_quantization_grid_edge_aliasing():
    """Regression for the ε-boundary bug class: values straddling a 1e-4
    grid edge within half a quantum alias to the same cell (round-half-even
    at exact midpoints); values a full quantum away never do. Documented in
    serve/cache.py."""
    # both sides of the 0.3 grid edge, within half a quantum → alias
    assert quantize_eps(0.29995) == 0.3      # midpoint rounds to even cell
    assert quantize_eps(0.29996) == 0.3
    assert quantize_eps(0.30004) == 0.3
    c = ResultCache(capacity=8)
    c.put("fp", 2, 0.29995, "cell-0.3")
    assert c.get("fp", 2, 0.30004) == "cell-0.3"
    assert c.get("fp", 2, 0.3) == "cell-0.3"
    # one full quantum away → distinct cells, no aliasing
    assert quantize_eps(0.2999) == 0.2999
    assert quantize_eps(0.3001) == 0.3001
    assert c.get("fp", 2, 0.2999) is None
    assert c.get("fp", 2, 0.3001) is None
    # the snap never moves ε by more than half a quantum (+ float slack),
    # and re-quantizing is a fixed point (grid values snap to themselves)
    for e in (0.0, 0.00005, 0.00015, 0.123456, 0.29995, 0.5, 0.99995, 1.0):
        q = quantize_eps(e)
        assert abs(q - e) <= 0.5e-4 + 1e-12, e
        assert quantize_eps(q) == q, e


def test_engine_executes_quantized_eps_not_raw():
    """Quantization must gate *execution*, not just the cache key: the
    device call receives the snapped ε, so a cached answer and a computed
    answer for the same cell can never disagree."""
    g, idx, _ = _graph_and_index(n=50, deg=5.0, seed=6)
    engine = MicroBatchEngine(idx, g, config=EngineConfig(
        max_batch=4, flush_ms=5.0, warm_ahead=False))
    seen = []
    real_call = engine._device_call

    def spy(fp, index, graph, mus, epss):
        seen.append(np.asarray(epss).copy())
        return real_call(fp, index, graph, mus, epss)

    engine._device_call = spy

    async def main():
        async with engine:
            a, b = await asyncio.gather(engine.query(2, 0.29995),
                                        engine.query(2, 0.30004))
            return a, b

    a, b = asyncio.run(main())
    # both straddling requests fold into ONE executed slot at exactly 0.3
    assert engine.stats["deduped"] == 1
    assert engine.stats["device_queries"] == 1
    assert all(np.all(e == np.float32(0.3)) for e in seen)
    ref = query(idx, g, 2, 0.3)
    np.testing.assert_array_equal(a.labels, np.asarray(ref.labels))
    np.testing.assert_array_equal(b.labels, np.asarray(ref.labels))


def test_engine_batches_what_waits_past_its_deadline():
    """Requests already queued when the flush deadline passes ride the
    same device call: a pause of the event loop (a GC pass) between the
    first pickup and the drain must not push them to the next call. With
    ``flush_ms=0`` the deadline has passed at the first pickup."""
    g, idx, _ = _graph_and_index(n=60, deg=5.0, seed=4)
    engine = MicroBatchEngine(idx, g, config=EngineConfig(
        max_batch=8, flush_ms=0.0, warm_ahead=False))

    async def main():
        async with engine:
            return await asyncio.gather(*[engine.query(2, eps) for eps in
                                          (0.2, 0.3, 0.4, 0.5, 0.6)])

    asyncio.run(main())
    assert engine.stats["device_queries"] == 1
    assert engine.stats["requests"] == 5


def test_cache_fingerprint_invalidation():
    c = ResultCache(capacity=8)
    c.put("fp1", 2, 0.5, "a")
    c.put("fp1", 3, 0.5, "b")
    c.put("fp2", 2, 0.5, "c")
    assert c.invalidate("fp1") == 2
    assert c.get("fp2", 2, 0.5) == "c"
    assert c.get("fp1", 2, 0.5) is None


# --------------------------------------------------------------------------
# micro-batching engine
# --------------------------------------------------------------------------
def test_engine_concurrent_queries_match_direct():
    g, idx, _ = _graph_and_index(n=100, deg=8.0, seed=9)
    cfg = EngineConfig(max_batch=8, flush_ms=20.0)
    engine = MicroBatchEngine(idx, g, config=cfg)
    reqs = [(mu, eps) for mu in (2, 3, 4) for eps in (0.2, 0.4, 0.6, 0.8)]

    async def main():
        async with engine:
            return await asyncio.gather(
                *[engine.query(mu, eps) for mu, eps in reqs])

    outs = asyncio.run(main())
    for (mu, eps), out in zip(reqs, outs):
        ref = query(idx, g, mu, eps)
        np.testing.assert_array_equal(out.labels, np.asarray(ref.labels))
        assert int(out.n_clusters) == int(ref.n_clusters)
    st = engine.batch_stats()
    # 12 concurrent requests with max_batch=8 → at most a handful of
    # device calls, strictly fewer than one per request
    assert st["device_queries"] < len(reqs)
    assert st["requests"] == len(reqs)


def test_engine_caches_and_dedupes():
    g, idx, _ = _graph_and_index(n=60, deg=6.0, seed=4)
    engine = MicroBatchEngine(idx, g,
                              config=EngineConfig(max_batch=4, flush_ms=20.0))

    async def main():
        async with engine:
            a, b = await asyncio.gather(engine.query(2, 0.5),
                                        engine.query(2, 0.5))
            calls_after_first = engine.stats["device_queries"]
            c = await engine.query(2, 0.5)          # served from cache
            return a, b, c, calls_after_first

    a, b, c, calls = asyncio.run(main())
    assert calls == 1
    assert engine.stats["device_queries"] == 1
    assert engine.stats["cache_hits"] >= 1
    assert engine.stats["deduped"] >= 1
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.labels, c.labels)


def test_engine_survives_device_failure():
    """A failing device call rejects that batch's waiters; the collector
    stays alive and answers the next request."""
    g, idx, _ = _graph_and_index(n=40, deg=4.0, seed=11)
    engine = MicroBatchEngine(idx, g,
                              config=EngineConfig(max_batch=4, flush_ms=5.0))
    real_execute = engine._execute
    calls = {"n": 0}

    def flaky(fp, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected device failure")
        return real_execute(fp, batch)

    engine._execute = flaky

    async def main():
        async with engine:
            try:
                await engine.query(2, 0.5)
            except RuntimeError as e:
                assert "injected" in str(e)
            else:
                raise AssertionError("expected RuntimeError")
            return await engine.query(2, 0.5)   # loop must still be alive

    out = asyncio.run(main())
    ref = query(idx, g, 2, 0.5)
    np.testing.assert_array_equal(out.labels, np.asarray(ref.labels))


def test_engine_cached_results_do_not_pin_batch_arrays():
    """Cached rows must be copies, not views of the padded [max_batch, n]
    device output (a view pins max_batch× the memory per entry)."""
    g, idx, _ = _graph_and_index(n=40, deg=4.0, seed=12)
    engine = MicroBatchEngine(idx, g,
                              config=EngineConfig(max_batch=8, flush_ms=5.0))

    async def main():
        async with engine:
            return await engine.query(2, 0.5)

    out = asyncio.run(main())
    assert out.labels.base is None
    assert out.is_core.base is None
    assert out.labels.shape == (g.n,)


def test_engine_invalidates_on_new_fingerprint(tmp_path):
    """A rebuilt identical index keeps cache hits (same fingerprint);
    a different graph's engine never sees them (different key space)."""
    g, idx, _ = _graph_and_index(n=50, deg=6.0, seed=7)
    cache = ResultCache(capacity=64)
    e1 = MicroBatchEngine(idx, g, cache=cache)
    g2, idx2, _ = _graph_and_index(n=50, deg=6.0, seed=8)
    e2 = MicroBatchEngine(idx2, g2, cache=cache)
    assert e1.fingerprint != e2.fingerprint

    async def main():
        async with e1:
            await e1.query(2, 0.5)
        async with e2:
            await e2.query(2, 0.5)

    asyncio.run(main())
    assert e2.stats["cache_hits"] == 0
    # each engine's answer lives under its own fingerprint (no aliasing);
    # warm-ahead entries may add more keys, also fingerprint-scoped
    assert cache.peek(e1.fingerprint, 2, 0.5) is not None
    assert cache.peek(e2.fingerprint, 2, 0.5) is not None
