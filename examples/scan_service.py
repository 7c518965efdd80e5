"""SCAN-as-a-service: persist an index, reload it, sweep parameters in one
vmapped call, serve concurrent clients through the micro-batch engine —
then the giant-graph/production postures: the same sweep *sharded* over an
8-way device mesh, and two indexes routed through one engine with
per-index cache partitions and sweep-ahead warming.

    PYTHONPATH=src python examples/scan_service.py
"""
# the sharded-serve section below wants multiple devices; force 8 host
# devices BEFORE jax's backend initializes (importing is fine, device use
# is not; harmless when real accelerators exist). Host compute is split
# 8 ways for the WHOLE demo, so the timings printed below illustrate the
# flow, not single-device performance; the chip benchmark under bench/
# measures that.
from repro.core.distributed import force_host_devices

force_host_devices(8)

import asyncio
import tempfile
import time

import numpy as np

from repro.core import build_index, query, query_batch, random_graph
from repro.serve import (EngineConfig, IndexStore, MicroBatchEngine,
                         sweep_stats)


def main():
    # --- build once, persist (the GS*-Index amortization story) ---
    g = random_graph(4000, 24.0, seed=7, planted_clusters=8)
    t0 = time.time()
    index = build_index(g, measure="cosine")
    print(f"index built in {time.time() - t0:.2f}s (n={g.n}, m={g.m})")

    with tempfile.TemporaryDirectory() as d:
        store = IndexStore(d)
        store.save(index, g)
        index, g, fp = store.load()     # a fresh process would start here
        print(f"reloaded version {store.latest_version()}, "
              f"fingerprint {fp[:12]}…")

        # --- explore settings: one compiled call for the whole grid ---
        rows = sweep_stats(index, g, [2, 4, 8], [0.2, 0.4, 0.6])
        best = max(rows, key=lambda r: r["modularity"])
        for r in rows:
            print(f"  mu={r['mu']} eps={r['eps']:.1f}: "
                  f"clusters={r['n_clusters']:4d} "
                  f"modularity={r['modularity']:.3f}")
        print(f"best: mu={best['mu']} eps={best['eps']:.1f}")

        # --- concurrent single queries, coalesced on the device ---
        engine = MicroBatchEngine(index, g, fingerprint=fp,
                                  config=EngineConfig(max_batch=8))

        async def client(mu, eps):
            res = await engine.query(mu, eps)
            return int(res.n_clusters)

        async def serve():
            async with engine:
                reqs = [(mu, eps) for mu in (2, 4, 8)
                        for eps in (0.2, 0.3, 0.4, 0.5, 0.6)]
                counts = await asyncio.gather(
                    *[client(mu, eps) for mu, eps in reqs])
                return counts

        counts = asyncio.run(serve())
        st = engine.batch_stats()
        print(f"{st['requests']} concurrent queries → "
              f"{st['device_queries']} device calls "
              f"(avg batch {st['avg_batch']:.1f}); "
              f"cluster counts {sorted(set(counts))}")

        # engine answers match direct queries
        r = query(index, g, best["mu"], best["eps"])
        assert int(r.n_clusters) == best["n_clusters"]
        print("consistency with direct query: OK")

    # ------------------------------------------------------------------
    # sharded serve: the giant-graph posture
    # ------------------------------------------------------------------
    # When one device can't hold the O(m) edge arrays, the same queries run
    # with the half-edge and CO-slot arrays partitioned over the mesh
    # 'data' axis; connectivity finishes with all-reduced label
    # propagation. Results are bit-identical to the single-device path.
    import jax
    from repro.core import query_batch_sharded, query_mesh

    k = min(8, jax.device_count())
    mesh = query_mesh(k)
    mus = np.asarray([2, 4, 8], np.int32)
    epss = np.asarray([0.3, 0.5, 0.7], np.float32)
    ref = query_batch(index, g, mus, epss)
    out = query_batch_sharded(index, g, mus, epss, mesh=mesh)
    exact = all(
        np.array_equal(np.asarray(getattr(out, f)),
                       np.asarray(getattr(ref, f)))
        for f in ("labels", "is_core", "n_clusters"))
    print(f"sharded sweep over {k} devices: bit-exact match = {exact}")
    assert exact

    # ------------------------------------------------------------------
    # multi-index routing: one engine, many graphs
    # ------------------------------------------------------------------
    # Requests carry an index fingerprint; the collector buckets by
    # fingerprint and flushes each bucket as its own fixed-shape device
    # call. Each index gets its own LRU cache partition, and padding slots
    # pre-warm the (μ±1, ε±δ) neighborhood of observed traffic.
    router = MicroBatchEngine(config=EngineConfig(max_batch=8, flush_ms=2.0,
                                                  warm_ahead=True))
    fps = []
    for seed in (7, 8):
        gk = random_graph(1500, 16.0, seed=seed, planted_clusters=6)
        fps.append(router.register(build_index(gk, "cosine"), gk))

    async def routed():
        async with router:
            reqs = [(fps[i % 2], 3, 0.3 + 0.05 * (i % 5))
                    for i in range(24)]
            outs = await asyncio.gather(
                *[router.query(mu, eps, fingerprint=fpk)
                  for fpk, mu, eps in reqs])
            return outs

    asyncio.run(routed())
    st = router.batch_stats()
    print(f"routed {st['requests']} requests across {st['indexes']} indexes"
          f" → {st['device_queries']} device calls, "
          f"{st['cache_hits']} cache hits, {st['warmed']} warmed, "
          f"{st['cache_partitions']} cache partitions")

    # ------------------------------------------------------------------
    # live updates: the resident update+query process
    # ------------------------------------------------------------------
    # Real graphs change. LiveIndexService applies edge-edit batches to a
    # resident index *incrementally* (σ recomputed only on the frontier of
    # touched endpoints — bit-identical to a full rebuild), hot-swaps the
    # result into the router atomically (in-flight queries finish on the
    # old index), persists every delta as a crash-safe chain, and compacts
    # the chain into a full snapshot periodically.
    from repro.core import build_index as rebuild_index
    from repro.core.update import EdgeDelta
    from repro.serve import LiveIndexService

    with tempfile.TemporaryDirectory() as d:
        svc = LiveIndexService(d, config=EngineConfig(max_batch=8),
                               compact_every=2)
        gl = random_graph(1200, 12.0, seed=11, planted_clusters=5)
        svc.create("social", gl)

        async def live_demo():
            async with svc:
                before = await svc.query("social", 3, 0.4)
                info = await svc.apply("social", EdgeDelta.make(
                    inserts=[(0, 600), (1, 700), (2, 800)],
                    weights=[0.9, 0.8, 0.7],
                    deletes=[(int(gl.edge_u[0]), int(gl.nbrs[0]))]))
                after = await svc.query("social", 3, 0.4)
                # second batch crosses compact_every=2 → full snapshot
                await svc.apply("social", EdgeDelta.make(
                    inserts=[(5, 900)], weights=[0.5]))
                return before, after, info

        before, after, info = asyncio.run(live_demo())
        status = svc.status("social")
        print(f"live update: {info.n_inserted} ins + {info.n_deleted} del "
              f"→ σ recomputed for {info.n_frontier}/"
              f"{2 * status['m']} half-edges (clusters "
              f"{int(before.n_clusters)} → {int(after.n_clusters)})")

        # the maintained index is bit-identical to a from-scratch rebuild
        rebuilt = rebuild_index(svc.graph("social"), "cosine")
        assert np.array_equal(np.asarray(rebuilt.no_sims),
                              np.asarray(svc.index("social").no_sims))
        print("incremental == rebuild (bit-identical): OK")

        # compaction snapshotted at the live fingerprint; a fresh process
        # restores straight from it
        assert (svc.catalog.store("social").latest_version()
                == status["seq"])
        svc2 = LiveIndexService(d)
        assert svc2.load("social") == status["fingerprint"]
        print(f"restored v{status['seq']} after compaction, fingerprint "
              f"{status['fingerprint'][:12]}… matches: OK")


if __name__ == "__main__":
    main()
