"""SCAN query service launcher.

Build (or load) persistent SCAN indexes, then either print a (μ, ε)
parameter-sweep table — optionally sharded over a device mesh — or run the
micro-batching engine under synthetic concurrent traffic, optionally
routing several indexes through one engine:

    # build an index, persist it, sweep a parameter grid
    PYTHONPATH=src python -m repro.launch.scan_serve sweep \
        --n 8192 --avg-degree 16 --save /tmp/scan_idx \
        --mus 2,4,8 --epss 0.2:0.8:7

    # the same sweep with the edge arrays sharded over 8 host devices
    PYTHONPATH=src python -m repro.launch.scan_serve sweep --shards 8

    # reload the persisted index and serve concurrent clients
    PYTHONPATH=src python -m repro.launch.scan_serve serve \
        --load /tmp/scan_idx --clients 32 --requests 64 --max-batch 32

    # one engine, three indexes, mixed-fingerprint traffic
    PYTHONPATH=src python -m repro.launch.scan_serve serve --indexes 3

    # seed-set (local) traffic: every client asks for single vertices'
    # clusters via query_seed(v, μ, ε) instead of full clusterings
    PYTHONPATH=src python -m repro.launch.scan_serve serve \
        --traffic seed --n 8192 --clients 32

    # 50/50 seed + global traffic against the live-update service: the
    # seed cache survives each delta through frontier migration
    PYTHONPATH=src python -m repro.launch.scan_serve update \
        --traffic mixed --updates 8

    # resident live-update process: a synthetic edit stream mutates the
    # graph while concurrent clients keep querying it
    PYTHONPATH=src python -m repro.launch.scan_serve update \
        --n 4096 --updates 16 --update-batch 8 --clients 8

    # approximate-first ingest: serve an LSH-sketched index immediately,
    # refine to the exact index in the background, hot-swap when it lands
    PYTHONPATH=src python -m repro.launch.scan_serve serve \
        --approx simhash:256 --n 8192 --clients 16

    # sweep a (μ, ε) grid against a sketched index (paper §5/§6.3)
    PYTHONPATH=src python -m repro.launch.scan_serve sweep \
        --approx simhash:128 --n 8192

    # replicated read fleet under chaos: one writer + 3 replicas tail
    # the DeltaLog while a seeded fault schedule crashes/stalls/corrupts;
    # exits nonzero if any answer diverges from the writer's bits or a
    # timeout escapes the admission/retry machinery unshed
    PYTHONPATH=src python -m repro.launch.scan_serve fleet \
        --replicas 3 --updates 8 --chaos crash:0.02,stall:0.05,corrupt:0.1

``--shards K`` forces K host-platform devices itself when jax would
otherwise see fewer (same effect as
``XLA_FLAGS=--xla_force_host_platform_device_count=K``).

Telemetry (``serve`` and ``update``): the engine's metrics registry
(counters, gauges, and the latency histograms behind every span — see
ROADMAP.md § Observability) is always live; ``--stats-every S`` prints a
compact one-line dump of it every S seconds while traffic runs, and
``--metrics-json PATH`` writes the full registry snapshot (JSON, incl.
per-bucket histogram counts) when the run finishes. Both runs also print
p50/p90/p99 queue-wait and end-to-end latency measured from the real
request histograms.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import os
import time

import numpy as np


def _fmt_latency(st: dict) -> str:
    """One line of queue-wait / e2e quantiles (``engine.latency_stats``)."""
    return (f"latency: e2e p50={st['e2e_p50'] * 1e3:.2f}ms "
            f"p90={st['e2e_p90'] * 1e3:.2f}ms "
            f"p99={st['e2e_p99'] * 1e3:.2f}ms (n={st['e2e_n']}); "
            f"queue-wait p50={st['wait_p50'] * 1e3:.2f}ms "
            f"p99={st['wait_p99'] * 1e3:.2f}ms (n={st['wait_n']})")


_SEED_SHARE = {"global": 0.0, "seed": 1.0, "mixed": 0.5}


def _fmt_seed_report(bst: dict, lst: dict) -> str:
    """Two lines of seed-path counters + latency (``--traffic seed|mixed``)."""
    return (f"seed path: requests={bst['seed_requests']} "
            f"device_calls={bst['seed_device_queries']} "
            f"buckets={bst['seed_batches']} "
            f"cache_hits={bst['seed_cache_hits']} "
            f"deduped={bst['seed_deduped']} spills={bst['seed_spills']} "
            f"warmed={bst['seed_warmed']}\n"
            f"seed latency: e2e p50={lst['seed_e2e_p50'] * 1e3:.2f}ms "
            f"p99={lst['seed_e2e_p99'] * 1e3:.2f}ms "
            f"(n={lst['seed_e2e_n']}); "
            f"queue-wait p50={lst['seed_wait_p50'] * 1e3:.2f}ms "
            f"(n={lst['seed_wait_n']})")


@contextlib.asynccontextmanager
async def _periodic_stats(registry, every: float):
    """Run the obs dump loop alongside traffic when ``--stats-every`` > 0."""
    from repro.obs import dump_loop

    task = None
    if every and every > 0:
        task = asyncio.get_running_loop().create_task(
            dump_loop(registry, every))
    try:
        yield
    finally:
        if task is not None:
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task


def _write_metrics(registry, path) -> None:
    if not path:
        return
    from repro.obs import write_json

    write_json(registry.snapshot(), path)
    print(f"wrote metrics snapshot to {path}")


def parse_values(spec: str, kind):
    """``"2,4,8"`` → list, or ``"0.1:0.9:5"`` → linspace."""
    if ":" in spec:
        lo, hi, num = spec.split(":")
        return [kind(v) for v in np.linspace(float(lo), float(hi), int(num))]
    return [kind(v) for v in spec.split(",")]


def get_index(args, *, seed=None):
    from repro.core import build_index, random_graph
    from repro.serve import IndexStore, index_fingerprint

    if args.load:
        store = IndexStore(args.load)
        index, g, fp = store.load()
        print(f"loaded index v{store.latest_version()} from {args.load} "
              f"(n={g.n}, m={g.m}, fingerprint={fp[:12]}, "
              f"{store.provenance().describe()})")
        return index, g, fp
    seed = args.seed if seed is None else seed
    g = random_graph(args.n, args.avg_degree, seed=seed,
                     weighted=args.weighted,
                     planted_clusters=args.clusters)
    t0 = time.time()
    provenance = None
    if getattr(args, "approx", None):
        from repro.core import ApproxIndexBuilder, ApproxParams
        builder = ApproxIndexBuilder(args.measure,
                                     ApproxParams.parse(args.approx))
        index, provenance = builder.build(g)
    else:
        index = build_index(g, args.measure)
    fp = index_fingerprint(index, g)
    kind = provenance.describe() if provenance is not None else "exact"
    print(f"built {kind} index in {time.time() - t0:.2f}s "
          f"(n={g.n}, m={g.m}, seed={seed}, fingerprint={fp[:12]})")
    if args.save:
        path = IndexStore(args.save).save(index, g, measure=args.measure,
                                          provenance=provenance)
        print(f"persisted to {path}")
    return index, g, fp


def cmd_sweep(args):
    from repro.serve import sweep_stats

    index, g, _ = get_index(args)
    mus = parse_values(args.mus, int)
    epss = parse_values(args.epss, float)
    mesh = None
    if args.shards > 1:
        import jax
        from repro.core import query_mesh
        mesh = query_mesh(args.shards)
        print(f"sharded sweep: edge arrays over {args.shards} of "
              f"{jax.device_count()} devices (axis 'data')")
    t0 = time.time()
    rows = sweep_stats(index, g, mus, epss, mesh=mesh)
    dt = time.time() - t0
    shard_note = f", {args.shards} shards" if mesh is not None else ""
    print(f"\n{len(rows)} (μ, ε) settings in one vmapped call "
          f"({dt:.2f}s incl. compile{shard_note})")
    print(f"{'mu':>4} {'eps':>6} {'clusters':>9} {'cores':>7} "
          f"{'coverage':>9} {'modularity':>11}")
    for r in rows:
        print(f"{r['mu']:>4} {r['eps']:>6.2f} {r['n_clusters']:>9} "
              f"{r['n_cores']:>7} {r['coverage']:>9.3f} "
              f"{r['modularity']:>11.4f}")
    best = max(rows, key=lambda r: r["modularity"])
    print(f"best modularity: mu={best['mu']} eps={best['eps']:.2f} "
          f"Q={best['modularity']:.4f}")


def cmd_serve(args):
    from repro.serve import EngineConfig, MicroBatchEngine

    if args.load and args.indexes > 1:
        raise SystemExit(
            "--indexes K>1 builds K distinct graphs and cannot be combined "
            "with --load (a persisted directory holds one index)")
    cfg = EngineConfig(max_batch=args.max_batch, flush_ms=args.flush_ms,
                       warm_ahead=not args.no_warm,
                       shards=args.shards if args.shards > 1 else None,
                       lane=args.lane)
    if args.approx:
        if args.load:
            raise SystemExit(
                "--approx builds a fresh LSH-sketched index and cannot be "
                "combined with --load (the loaded artifact is already "
                "built; its provenance travels with it)")
        return _serve_approx(args, cfg)
    engine = MicroBatchEngine(config=cfg)
    catalog = None
    if args.indexes > 1 and args.save:
        # K indexes need K named stores, not K versions of one store (only
        # the last version would survive a --load); route through a catalog
        from repro.serve import IndexCatalog
        catalog = IndexCatalog(args.save)
        args.save = None
    fps, sizes = [], []
    for k in range(max(args.indexes, 1)):
        index, g, fp = get_index(args, seed=args.seed + k)
        if catalog is not None:
            path = catalog.save(f"idx{k}", index, g, measure=args.measure)
            print(f"persisted to {path}")
        fps.append(engine.register(index, g, fingerprint=fp))
        sizes.append(g.n)
    rng = np.random.default_rng(0)
    pool = [(int(m), float(e))
            for m in (2, 3, 4, 5, 8)
            for e in np.round(np.linspace(0.1, 0.9, 17), 3)]
    seed_share = _SEED_SHARE[args.traffic]

    async def client(cid: int):
        for _ in range(args.requests):
            mu, eps = pool[rng.integers(len(pool))]
            k = rng.integers(len(fps))
            if rng.random() < seed_share:
                res = await engine.query_seed(int(rng.integers(sizes[k])),
                                              mu, eps, fingerprint=fps[k])
            else:
                res = await engine.query(mu, eps, fingerprint=fps[k])
            del res
            await asyncio.sleep(0)

    async def main():
        async with engine:
            # warm every index's compiled batch shape(s) before timing
            for fp in fps:
                if seed_share < 1.0:
                    await engine.query(*pool[0], fingerprint=fp)
                if seed_share > 0.0:
                    await engine.query_seed(0, *pool[0], fingerprint=fp)
            async with _periodic_stats(engine.registry, args.stats_every):
                t0 = time.time()
                await asyncio.gather(
                    *[client(i) for i in range(args.clients)])
                return time.time() - t0

    dt = asyncio.run(main())
    total = args.clients * args.requests
    st = engine.batch_stats()
    mode = f"{len(fps)} indexes" + (f", {args.shards} shards"
                                    if cfg.shards else "")
    print(f"\n{total} {args.traffic} requests from {args.clients} clients "
          f"({mode}) in {dt:.2f}s → {total / dt:.1f} req/s")
    print(f"device calls={st['device_queries']} buckets={st['batches']} "
          f"avg_batch={st['avg_batch']:.1f} cache_hits={st['cache_hits']} "
          f"deduped={st['deduped']} warmed={st['warmed']} "
          f"hit_rate={st['cache_hit_rate']:.2f} "
          f"partitions={st['cache_partitions']} "
          f"jit_recompiles={st['jit_recompiles']}")
    lst = engine.latency_stats()
    if seed_share > 0.0:
        print(_fmt_seed_report(st, lst))
    print(_fmt_latency(lst))
    _write_metrics(engine.registry, args.metrics_json)


def _serve_approx(args, cfg):
    """Approximate-first serve: LSH-sketched indexes answer traffic from
    second zero while exact refinement runs in the background and
    hot-swaps in behind the drain barrier (``--approx simhash:256``)."""
    import tempfile

    from repro.core import ApproxParams, random_graph
    from repro.serve import LiveIndexService

    params = ApproxParams.parse(args.approx)
    if params.measure != args.measure:
        raise SystemExit(
            f"--approx {params.method} estimates {params.measure} "
            f"similarity; pass --measure {params.measure}")
    root = args.save or tempfile.mkdtemp(prefix="scan_approx_")
    svc = LiveIndexService(root, config=cfg, measure=args.measure)
    names, sizes = [], []
    for k in range(max(args.indexes, 1)):
        g = random_graph(args.n, args.avg_degree, seed=args.seed + k,
                         weighted=args.weighted,
                         planted_clusters=args.clusters)
        name = f"idx{k}"
        t0 = time.time()
        fp = svc.register_approximate(name, g, params=params)
        print(f"approx index {name!r} built+serving in "
              f"{time.time() - t0:.2f}s (n={g.n}, m={g.m}, "
              f"fingerprint={fp[:12]}, "
              f"{svc.provenance(name).describe()}) → {root}")
        names.append(name)
        sizes.append(g.n)
    rng = np.random.default_rng(0)
    pool = [(int(m), float(e))
            for m in (2, 3, 4, 5, 8)
            for e in np.round(np.linspace(0.1, 0.9, 17), 3)]
    seed_share = _SEED_SHARE[args.traffic]
    refine_s = {}

    async def client(cid: int):
        for _ in range(args.requests):
            mu, eps = pool[rng.integers(len(pool))]
            k = rng.integers(len(names))
            if rng.random() < seed_share:
                await svc.query_seed(names[k],
                                     int(rng.integers(sizes[k])), mu, eps)
            else:
                await svc.query(names[k], mu, eps)
            await asyncio.sleep(0)

    async def refiner(name: str):
        t0 = time.time()
        await svc.refine(name)
        refine_s[name] = time.time() - t0

    async def main():
        async with svc:
            for name in names:
                await svc.query(name, *pool[0])   # warm the batch shape
                if seed_share > 0.0:
                    await svc.query_seed(name, 0, *pool[0])
            async with _periodic_stats(svc.engine.registry,
                                       args.stats_every):
                t0 = time.time()
                # refinement races the full traffic wave — queries are
                # served from σ̂ until each exact swap lands
                await asyncio.gather(
                    *[refiner(name) for name in names],
                    *[client(i) for i in range(args.clients)])
                return time.time() - t0

    dt = asyncio.run(main())
    total = args.clients * args.requests
    st = svc.stats()
    print(f"\n{total} queries from {args.clients} clients "
          f"({len(names)} approximate-first indexes) in {dt:.2f}s "
          f"→ {total / dt:.1f} q/s")
    for name in names:
        status = svc.status(name)
        print(f"  {name}: refined to exact in {refine_s[name]:.2f}s → "
              f"fingerprint={status['fingerprint'][:12]} "
              f"({status['provenance']}, seq={status['seq']})")
    print(f"device calls={st['device_queries']} cache_hits={st['cache_hits']} "
          f"warmed={st['warmed']} hit_rate={st['cache_hit_rate']:.2f} "
          f"approx_indexes_remaining={st['approx_indexes']}")
    if seed_share > 0.0:
        print(_fmt_seed_report(svc.engine.batch_stats(),
                               svc.engine.latency_stats()))
    reg = svc.engine.registry
    for span in ("index.approx_build", "live.refine", "live.refine_build"):
        hist = reg.histogram(span)
        if hist.count:
            print(f"{span}: p50={hist.quantile(0.5):.2f}s "
                  f"(n={hist.count})")
    print(_fmt_latency(svc.engine.latency_stats()))
    _write_metrics(reg, args.metrics_json)


def cmd_update(args):
    """Resident live-update demo: apply an edit stream while serving."""
    import tempfile

    from repro.core import random_graph
    from repro.core.update import random_delta
    from repro.serve import EngineConfig, IndexStore, LiveIndexService

    if args.save:
        raise SystemExit(
            "the update service persists snapshots + delta chains under "
            "its own catalog root; use --root DIR instead of --save")
    cfg = EngineConfig(max_batch=args.max_batch, flush_ms=args.flush_ms,
                       warm_ahead=not args.no_warm,
                       shards=args.shards if args.shards > 1 else None,
                       lane=args.lane)
    root = args.root or tempfile.mkdtemp(prefix="scan_live_")
    svc = LiveIndexService(root, config=cfg, measure=args.measure,
                           compact_every=args.compact_every)
    t0 = time.time()
    if args.load:
        store = IndexStore(args.load)
        stored = store.measure()
        if stored is not None and stored != args.measure:
            raise SystemExit(
                f"{args.load} was built with --measure {stored}; "
                f"updating it with --measure {args.measure} would mix "
                "similarity measures (pass the matching --measure)")
        index, g, _ = store.load()
        fp = svc.create("live", g, index=index)
        verb = f"adopted from {args.load}"
    else:
        g = random_graph(args.n, args.avg_degree, seed=args.seed,
                         weighted=args.weighted,
                         planted_clusters=args.clusters)
        fp = svc.create("live", g)
        verb = "built"
    print(f"live index {verb} in {time.time() - t0:.2f}s "
          f"(n={g.n}, m={g.m}, fingerprint={fp[:12]}) → {root}")

    rng = np.random.default_rng(args.seed + 1)
    pool = [(int(m), float(e))
            for m in (2, 3, 4, 5)
            for e in np.round(np.linspace(0.1, 0.9, 9), 3)]
    seed_share = _SEED_SHARE[args.traffic]
    apply_times, frontier_sizes = [], []

    async def editor():
        for _ in range(args.updates):
            delta = random_delta(svc.graph("live"), args.update_batch, rng)
            t0 = time.time()
            info = await svc.apply("live", delta)
            apply_times.append(time.time() - t0)
            frontier_sizes.append(info.n_frontier)
            await asyncio.sleep(0)

    async def client(cid: int):
        for _ in range(args.requests):
            mu, eps = pool[rng.integers(len(pool))]
            if rng.random() < seed_share:
                # seed entries ride through each delta via frontier
                # migration; n is stable under random_delta edit streams
                await svc.query_seed("live",
                                     int(rng.integers(g.n)), mu, eps)
            else:
                await svc.query("live", mu, eps)
            await asyncio.sleep(0)

    async def main_():
        async with svc:
            await svc.query("live", *pool[0])     # compile warmup
            if seed_share > 0.0:
                await svc.query_seed("live", 0, *pool[0])
            async with _periodic_stats(svc.engine.registry,
                                       args.stats_every):
                t0 = time.time()
                await asyncio.gather(
                    editor(), *[client(i) for i in range(args.clients)])
                return time.time() - t0

    dt = asyncio.run(main_())
    total = args.clients * args.requests
    st = svc.stats()
    status = svc.status("live")
    print(f"\n{total} queries under {args.updates} live update batches "
          f"(size {args.update_batch}) in {dt:.2f}s → {total / dt:.1f} q/s")
    print(f"updates: mean apply={np.mean(apply_times) * 1e3:.1f}ms "
          f"mean frontier={np.mean(frontier_sizes):.0f} half-edges; "
          f"final seq={status['seq']} "
          f"snapshot_seq={status['snapshot_seq']} "
          f"fingerprint={status['fingerprint'][:12]}")
    print(f"engine: device calls={st['device_queries']} "
          f"cache_hits={st['cache_hits']} warmed={st['warmed']} "
          f"hit_rate={st['cache_hit_rate']:.2f} "
          f"partitions={st['cache_partitions']} "
          f"jit_recompiles={st['jit_recompiles']}")
    lst = svc.engine.latency_stats()
    if seed_share > 0.0:
        reg = svc.engine.registry
        print(_fmt_seed_report(svc.engine.batch_stats(), lst))
        print(f"seed cache vs deltas: migrated="
              f"{reg.counter('live.seed_entries_migrated').value} "
              f"dropped={reg.counter('live.seed_entries_dropped').value}")
    print(_fmt_latency(lst))
    apply_hist = svc.engine.registry.histogram("live.apply_delta")
    if apply_hist.count:
        print(f"apply pipeline: apply_delta p50="
              f"{apply_hist.quantile(0.5) * 1e3:.1f}ms "
              f"p99={apply_hist.quantile(0.99) * 1e3:.1f}ms "
              f"(n={apply_hist.count}); offload jobs="
              f"{svc.engine.registry.counter('engine.offload_jobs').value}")
    _write_metrics(svc.engine.registry, args.metrics_json)


def cmd_fleet(args):
    """Replicated-fleet verification run: writer + N tailing replicas +
    router under synthetic traffic and (optionally) a seeded chaos
    schedule, with a bit-identity oracle over every routed answer."""
    import tempfile

    from repro.core import random_graph
    from repro.core.update import random_delta
    from repro.serve import (ChaosPolicy, EngineConfig, Fleet,
                             FleetExhausted, Overloaded, RouterConfig)
    from repro.obs import write_json

    if args.load or args.save:
        raise SystemExit(
            "the fleet serves its own catalog root (snapshots + delta "
            "chains for writer and replicas alike); use --root DIR")
    chaos = None
    if args.chaos:
        chaos = ChaosPolicy.parse(args.chaos, seed=args.chaos_seed)
        print(f"armed {chaos.describe()}")
    cfg = EngineConfig(max_batch=args.max_batch, flush_ms=args.flush_ms,
                       warm_ahead=not args.no_warm, lane=args.lane)
    root = args.root or tempfile.mkdtemp(prefix="scan_fleet_")
    fleet = Fleet(root, n_replicas=args.replicas, writer_config=cfg,
                  router_config=RouterConfig(timeout_s=args.timeout_s,
                                             hedge_after_s=args.hedge_after),
                  measure=args.measure, compact_every=args.compact_every,
                  chaos=chaos)
    g = random_graph(args.n, args.avg_degree, seed=args.seed,
                     weighted=args.weighted,
                     planted_clusters=args.clusters)
    rng = np.random.default_rng(args.seed + 1)
    pool = [(int(m), float(e))
            for m in (2, 3, 4, 5)
            for e in np.round(np.linspace(0.1, 0.9, 9), 3)]
    seed_share = _SEED_SHARE[args.traffic]
    # oracle: the writer records each seq's content fingerprint the
    # moment the delta commits; any answer must match the fingerprint
    # recorded at *its* seq (stale is legal, divergent bits are not)
    oracle_fp = {}
    tally = {"ok": 0, "stale": 0, "shed": 0, "unavailable": 0,
             "divergent": 0, "unshed_timeouts": 0}

    async def editor():
        for _ in range(args.updates):
            delta = random_delta(fleet.writer.graph("g"),
                                 args.update_batch, rng)
            await fleet.apply("g", delta)
            oracle_fp[fleet.target_seq("g")] = fleet.writer.fingerprint("g")
            await asyncio.sleep(0)

    async def client(cid: int):
        for _ in range(args.requests):
            mu, eps = pool[rng.integers(len(pool))]
            if rng.random() < seed_share:
                coro = fleet.query_seed("g", int(rng.integers(g.n)), mu, eps)
            else:
                coro = fleet.query("g", mu, eps)
            try:
                # guard-s is the *unshed* timeout detector: the router's
                # own timeout/retry budget is far below it, so tripping
                # the guard means a request escaped every typed exit
                ans = await asyncio.wait_for(coro, args.guard_s)
            except Overloaded:
                tally["shed"] += 1
            except FleetExhausted:
                tally["unavailable"] += 1
            except asyncio.TimeoutError:
                tally["unshed_timeouts"] += 1
            else:
                want = oracle_fp.get(ans.seq)
                if want is None or ans.fingerprint != want:
                    tally["divergent"] += 1
                    print(f"DIVERGENT answer: replica={ans.replica} "
                          f"seq={ans.seq} fp={ans.fingerprint[:12]} "
                          f"oracle={'missing' if want is None else want[:12]}")
                else:
                    tally["ok"] += 1
                    if ans.seq < max(oracle_fp):
                        tally["stale"] += 1
            await asyncio.sleep(0)

    async def main_():
        async with fleet:
            fleet.create("g", g)
            oracle_fp[0] = fleet.writer.fingerprint("g")
            # wait for every replica to discover + restore the snapshot,
            # then warm the compiled batch shapes through the router
            await fleet.converged("g", timeout_s=30.0)
            if seed_share < 1.0:
                await fleet.query("g", *pool[0])
            if seed_share > 0.0:
                await fleet.query_seed("g", 0, *pool[0])
            async with _periodic_stats(fleet.registry, args.stats_every):
                t0 = time.time()
                await asyncio.gather(
                    editor(), *[client(i) for i in range(args.clients)])
                dt = time.time() - t0
            settled = await fleet.converged("g", timeout_s=5.0)
            rows = [(rep.replica_id, rep.healthy, rep.crashed,
                     rep.seq("g") if "g" in rep._tracked else None)
                    for rep in fleet.replicas]
            return dt, settled, rows

    dt, settled, rows = asyncio.run(main_())
    total = sum(tally[k] for k in
                ("ok", "shed", "unavailable", "divergent", "unshed_timeouts"))
    snap = fleet.metrics_snapshot()
    c = snap.get("counters", {})
    print(f"\n{total} {args.traffic} requests from {args.clients} clients "
          f"over {args.replicas} replicas ({args.updates} deltas applied) "
          f"in {dt:.2f}s → {total / dt:.1f} req/s")
    print(f"answers: ok={tally['ok']} (stale-but-consistent="
          f"{tally['stale']}) shed={tally['shed']} "
          f"unavailable={tally['unavailable']} "
          f"divergent={tally['divergent']} "
          f"unshed_timeouts={tally['unshed_timeouts']}")
    print(f"router: requests={c.get('fleet.requests', 0)} "
          f"failovers={c.get('fleet.failovers', 0)} "
          f"retries={c.get('fleet.retries', 0)} "
          f"hedges={c.get('fleet.hedges', 0)} "
          f"hedge_wins={c.get('fleet.hedge_wins', 0)} "
          f"overload_spills={c.get('fleet.overload_spills', 0)} "
          f"exhausted={c.get('fleet.exhausted', 0)}")
    print(f"replication: replays={c.get('fleet.replays', 0)} "
          f"swaps={c.get('fleet.swaps', 0)} "
          f"resyncs={c.get('fleet.resyncs', 0)} "
          f"corrupt_entries={c.get('fleet.corrupt_entries', 0)} "
          f"fingerprint_mismatches="
          f"{c.get('fleet.fingerprint_mismatches', 0)} "
          f"injected_corruptions={c.get('fleet.injected_corruptions', 0)} "
          f"crashes={c.get('fleet.crashes', 0)} "
          f"stalls={c.get('fleet.stalls', 0)}")
    target = fleet.target_seq("g")
    for rid, healthy, crashed, pos in rows:
        print(f"  {rid}: healthy={healthy} crashed={crashed} "
              f"seq={pos if pos is not None else '-'}/{target}")
    note = "converged" if settled else \
        "NOT converged (last-good service continues; staleness gauge " \
        f"= {snap.get('gauges', {}).get('fleet.staleness_seq', 0):g})"
    print(f"fleet {note}; writer at seq {target}")
    if args.metrics_json:
        write_json(snap, args.metrics_json)
        print(f"wrote merged fleet metrics snapshot to {args.metrics_json}")
    if tally["divergent"] or tally["unshed_timeouts"]:
        raise SystemExit(
            f"FLEET CHECK FAILED: divergent={tally['divergent']} "
            f"unshed_timeouts={tally['unshed_timeouts']}")
    print("fleet check passed: every answer carried the writer's exact "
          "bits for its sequence number")


_FLEET_EPILOG = """\
worked example — a chaos soak that must exit 0:

    PYTHONPATH=src python -m repro.launch.scan_serve fleet \\
        --n 2048 --avg-degree 8 --replicas 3 --clients 8 --requests 16 \\
        --updates 8 --chaos crash:0.02,stall:0.05,corrupt:0.1 \\
        --chaos-seed 7 --metrics-json /tmp/fleet_metrics.json

Every routed answer carries (fingerprint, seq, replica); the run fails
(exit 1) if any answer's fingerprint differs from the one the writer
recorded at that seq — bit divergence — or if a request times out
without a typed Overloaded/FleetExhausted exit. Stale answers (an older
seq than the writer's tip) are legal and reported separately; the
`fleet.staleness_seq` gauge in --metrics-json is the fleet-wide
watermark. Chaos spec keys: crash, stall, slow, corrupt, delay
(values are probabilities; the schedule is fully determined by
--chaos-seed, so a failing seed is a regression test)."""


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name, fn in (("sweep", cmd_sweep), ("serve", cmd_serve),
                     ("update", cmd_update), ("fleet", cmd_fleet)):
        p = sub.add_parser(
            name,
            epilog=_FLEET_EPILOG if name == "fleet" else None,
            formatter_class=argparse.RawDescriptionHelpFormatter)
        p.set_defaults(fn=fn)
        p.add_argument("--load", help="load a persisted index directory")
        p.add_argument("--save", help="persist the built index here")
        p.add_argument("--n", type=int, default=8192)
        p.add_argument("--avg-degree", type=float, default=16.0)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--clusters", type=int, default=0)
        p.add_argument("--weighted", action="store_true")
        p.add_argument("--measure", default="cosine")
        p.add_argument("--shards", type=int, default=0,
                       help="shard the query path over K devices")
        p.add_argument("--lane",
                       choices=("ref", "pallas-interpret", "pallas-compiled"),
                       help="force every kernel onto one execution lane: "
                       "'ref' is the pure-jnp oracle, 'pallas-interpret' "
                       "emulates the Pallas kernel bodies on host, "
                       "'pallas-compiled' dispatches them to the "
                       "accelerator. All lanes are bit-identical on "
                       "unweighted graphs (ULP-close on weighted), so "
                       "this is a debugging/benchmarking knob, not a "
                       "quality one. Default: auto per call (the "
                       "REPRO_LANE env var overrides either way)")
        if name in ("sweep", "serve"):
            p.add_argument("--approx", metavar="METHOD[:K[:SEED]]",
                           help="build LSH-sketched (approximate-first) "
                           "indexes, e.g. simhash:256 or minhash:128:7; "
                           "under `serve` the exact index is refined in "
                           "the background and hot-swapped in while "
                           "traffic runs")
        if name == "sweep":
            p.add_argument("--mus", default="2,4,8")
            p.add_argument("--epss", default="0.1:0.9:9")
        else:
            p.add_argument("--clients", type=int, default=16)
            p.add_argument("--requests", type=int, default=32)
            p.add_argument("--traffic", choices=("global", "seed", "mixed"),
                           default="global",
                           help="client workload shape: 'global' clusters "
                           "the whole graph per request (query(μ, ε)); "
                           "'seed' asks for single random vertices' "
                           "clusters (query_seed(v, μ, ε) — served by the "
                           "fixed-shape local frontier kernel + the "
                           "seed-keyed cache); 'mixed' draws 50/50 per "
                           "request. Under `update`, seed cache entries "
                           "survive deltas via frontier migration")
            p.add_argument("--max-batch", type=int, default=32)
            p.add_argument("--flush-ms", type=float, default=2.0)
            p.add_argument("--no-warm", action="store_true",
                           help="disable sweep-ahead cache warming")
            p.add_argument("--metrics-json", metavar="PATH",
                           help="write the engine's full metrics-registry "
                           "snapshot (counters, gauges, latency histogram "
                           "buckets) as JSON when the run finishes")
            p.add_argument("--stats-every", type=float, default=0.0,
                           metavar="SECONDS",
                           help="periodically print a one-line metrics "
                           "dump while traffic runs (0 = off)")
        if name == "serve":
            p.add_argument("--indexes", type=int, default=1,
                           help="serve K indexes through one engine")
        if name in ("update", "fleet"):
            p.add_argument("--root", help="service catalog root "
                           "(snapshots + delta chains; default: tempdir)")
            p.add_argument("--updates", type=int, default=16,
                           help="number of edit batches to apply")
            p.add_argument("--update-batch", type=int, default=8,
                           help="edits per batch (half ins, half del)")
            p.add_argument("--compact-every", type=int, default=8,
                           help="snapshot + prune after this many deltas")
        if name == "fleet":
            p.add_argument("--replicas", type=int, default=3,
                           help="read replicas tailing the writer's chain")
            p.add_argument("--chaos", metavar="SPEC",
                           help="seeded fault schedule, e.g. "
                           "crash:0.02,stall:0.05,corrupt:0.1 (keys: "
                           "crash, stall, slow, corrupt, delay; values "
                           "are probabilities)")
            p.add_argument("--chaos-seed", type=int, default=0,
                           help="rng seed for the chaos schedule (a "
                           "failing seed replays exactly)")
            p.add_argument("--timeout-s", type=float, default=5.0,
                           help="router per-attempt timeout")
            p.add_argument("--hedge-after", type=float, default=0.25,
                           help="race a sibling replica if the primary "
                           "has not answered within this many seconds")
            p.add_argument("--guard-s", type=float, default=30.0,
                           help="wall-clock guard per request; tripping "
                           "it counts as an *unshed* timeout and fails "
                           "the run")
    args = ap.parse_args(argv)
    if args.lane:
        # export rather than thread: the per-call REPRO_LANE read reaches
        # every dispatch site, including index *construction* paths that
        # run before any EngineConfig exists
        os.environ["REPRO_LANE"] = args.lane
    if getattr(args, "shards", 0) > 1:
        # must happen before jax's backend initializes — which is why all
        # heavier repro imports are deferred into the command functions
        from repro.core.distributed import force_host_devices
        force_host_devices(args.shards)
    from repro.backend.compile_cache import enable_compile_cache
    enable_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
