#!/usr/bin/env python3
"""Chip smoke test: the SCAN service's served path on one TPU chip.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the sharded paths on a 4-chip mesh

One process, no subprocess. Every check that fails exits nonzero; so does
a run that finds no TPU or a forced ``pallas-interpret`` lane. Progress,
phase seconds (compile shown apart from the rest) and check results go to
earlier lines; the last line of standard output is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One chip:

1. Exactness at small size: a 4,096-vertex power-law graph with a
   1,024-spoke hub. The index is built on the chip under the auto lanes;
   its σ equals a ref-lane build on every half-edge, cores and labels
   match ``core/scan_ref.py`` over a (μ, ε) grid, and 64 seeds'
   ``query_seeds`` rows equal their rows of the full ``query``.
2. Full size, through ``LiveIndexService`` → ``MicroBatchEngine``: a
   2²⁰-vertex Chung–Lu power-law graph (α = 2.1, ~8.3M edges, mean degree
   ~16, expected degrees capped at 4,096, one 16,384-spoke hub =
   8 × HUB_TILE): a quarter of SNAP com-LiveJournal's 3,997,962 vertices
   at about its mean degree of 17.3. ``create`` (exact build), 64 global +
   64 seed requests served concurrently, two 64-edit ``random_delta``
   batches, 8 + 8 more requests, ``register_approximate`` (simhash:256)
   of the edited graph on a second name, 8 global requests from it,
   ``refine``. Checks: compiled-lane counters of ``bucket_probe`` and
   ``hamming`` above 0 and no interpret counter; σ under the auto lanes
   bit-identical to the ref lane on 2,048 edges of every group; every
   served answer (global labels and cores, seed rows) equal to
   ``query_batch``; σ̂ of the compiled lane equal to the ref lane's on
   every sketched half-edge; the refine (a from-scratch exact build of the
   edited graph) equal in fingerprint to the index the deltas maintained.

``--chips 4``, at the same full size (``--small``: on the 4,096-vertex
graph): ``sharded_edge_similarities`` and ``query_batch_sharded`` on a
4-chip mesh, and an ``EngineConfig(shards=4)`` engine, each bit-identical
to the one-chip results on device 0.

Writes only under ``<repo>/.smoke_catalog`` (snapshots and delta chains,
cleared at start) and the compile cache (``repro.backend.compile_cache``).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

CATALOG = ROOT / ".smoke_catalog"
SMALL_GRAPH = dict(n=4096, alpha=2.1, avg_degree=8, hub_degree=1024, seed=7)
# the generator draws n·avg_degree edges, so avg_degree=8 gives a mean
# degree of ~16 (see power_law_graph)
FULL_GRAPH = dict(n=2 ** 20, alpha=2.1, avg_degree=8, hub_degree=16384,
                  max_degree=4096, seed=7)
MU_EPS_GRID = [(mu, eps) for mu in (2, 3, 5, 8) for eps in (0.2, 0.4, 0.6, 0.8)]
# the (μ, ε) pool the CLI's update/serve clients draw from
POOL = [(mu, float(eps)) for mu in (2, 3, 4, 5)
        for eps in (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)]
N_GLOBAL = 64
N_SEED = 64
N_AFTER = 8     # global requests after the deltas and from the
                # approximate index
DELTA_BATCHES = 2
DELTA_EDITS = 64
APPROX = "simhash:256"
# edges per (P, T) group whose σ the full-size run recomputes
# on the ref lane (the whole ref-lane build there takes ~530 s on one v5e;
# the small graph compares every edge)
REF_SAMPLE = 2048


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {what}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class Clock:
    """Wall seconds per phase, with the seconds XLA spent compiling inside
    it shown apart (``backend_compile`` events; a persistent-cache hit
    counts its load time)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._compile = 0.0
        self._by_fun: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compile += duration
                self._by_fun[fun_name] = (self._by_fun.get(fun_name, 0.0)
                                          + duration)

    def report_compiles(self, top: int = 8) -> None:
        """The functions that took longest to compile, summed over shapes."""
        worst = sorted(self._by_fun.items(), key=lambda kv: -kv[1])[:top]
        print("compile seconds by function: " + ", ".join(
            f"{name}={sec:.2f}" for name, sec in worst), flush=True)

    def phase(self, name: str):
        clock = self

        class _Phase:
            def __enter__(self):
                self.t0, self.c0 = time.perf_counter(), clock._compile
                return self

            def __exit__(self, *exc):
                wall = time.perf_counter() - self.t0
                comp = clock._compile - self.c0
                print(f"phase {name}: {wall:.2f}s wall, {comp:.2f}s compile, "
                      f"{max(wall - comp, 0.0):.2f}s other", flush=True)
        return _Phase()


def ref_policy():
    from repro.backend.policy import LANE_REF, ExecutionPolicy
    from repro.obs import MetricsRegistry
    return ExecutionPolicy(forced_lane=LANE_REF, registry=MetricsRegistry())


def build_on_lane(g, policy):
    """``build_index`` under ``policy`` (it takes none: the process default
    decides, so swap the default around the call)."""
    from repro.backend.policy import default_policy, set_default_policy
    from repro.core import build_index
    prev = default_policy()
    set_default_policy(policy)
    try:
        return build_index(g)
    finally:
        set_default_policy(prev)


def kernel_shapes(plan) -> dict:
    """{(probe width, target width): edges} of the groups the last
    ``edge_sims`` sent to the compiled bucket_probe lane."""
    from repro.backend.policy import LANE_COMPILED
    out = {}
    for pe, te, lane, edges in plan.last_group_shapes:
        if lane == LANE_COMPILED:
            out[(pe, te)] = out.get((pe, te), 0) + edges
    return dict(sorted(out.items()))


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def small_exactness(clock: Clock) -> None:
    import numpy as np
    from repro.core import (build_index, plan_for, power_law_graph, query,
                            query_seeds)
    from repro.core.scan_ref import scan_ref

    g = power_law_graph(**SMALL_GRAPH)
    print(f"small graph: n={g.n} m={g.m} max_degree="
          f"{int(np.diff(np.asarray(g.offsets)).max())}", flush=True)
    with clock.phase("small.build"):
        idx = build_index(g)
        sims = np.asarray(idx.edge_sims)
    print(f"small bucket_probe groups (P, T): edges = "
          f"{kernel_shapes(plan_for(g))}", flush=True)
    with clock.phase("small.build (ref lane)"):
        ref = build_on_lane(g, ref_policy())
    check(np.array_equal(sims.view(np.uint32),
                         np.asarray(ref.edge_sims).view(np.uint32)),
          f"small sigma auto lanes == ref lane on all {g.m2} half-edges "
          f"(bit-identical)")
    with clock.phase("small.scan_ref"):
        for mu, eps in MU_EPS_GRID:
            res = query(idx, g, mu, eps)
            ref = scan_ref(g, mu, eps, "cosine", sims=sims)
            same = (np.array_equal(np.asarray(res.is_core), ref["is_core"])
                    and np.array_equal(np.asarray(res.labels),
                                       ref["labels"]))
            if not same:
                check(False, f"small cores+labels == scan_ref at "
                             f"mu={mu} eps={eps}")
    check(True, f"small cores+labels == scan_ref over "
                f"{len(MU_EPS_GRID)} (mu, eps)")
    rng = np.random.default_rng(SMALL_GRAPH["seed"])
    seeds = rng.choice(g.n, size=64, replace=False).astype(np.int32)
    with clock.phase("small.seeds"):
        for mu, eps in ((2, 0.4), (5, 0.6)):
            sb = query_seeds(idx, g, seeds, mu, eps)
            full = query(idx, g, mu, eps)
            labels = np.asarray(full.labels)
            want = np.stack([labels == labels[s] if labels[s] >= 0
                             else np.zeros(g.n, bool) for s in seeds])
            check(np.array_equal(sb.labels, labels[seeds])
                  and np.array_equal(sb.is_core,
                                     np.asarray(full.is_core)[seeds])
                  and np.array_equal(sb.member_mask, want),
                  f"small query_seeds rows == query rows (64 seeds, "
                  f"mu={mu} eps={eps})")


async def _serve(svc, name, rng, n, n_global, n_seed):
    """``n_global`` global + ``n_seed`` seed requests, all in flight at
    once; → ([((mu, eps_q), labels)], [(seed, mu, eps_q, SeedResult)]),
    one entry per request, in request order."""
    from repro.serve.cache import quantize_eps
    quantum = svc.engine.cfg.eps_quantum
    glob = [POOL[i] for i in rng.integers(len(POOL), size=n_global)]
    seed = [(int(v), *POOL[i]) for v, i in
            zip(rng.integers(n, size=n_seed), rng.integers(len(POOL),
                                                           size=n_seed))]
    answers = await asyncio.gather(
        *[svc.query(name, mu, eps) for mu, eps in glob],
        *[svc.query_seed(name, v, mu, eps) for v, mu, eps in seed])
    served = [((mu, quantize_eps(eps, quantum)), res)
              for (mu, eps), res in zip(glob, answers[:n_global])]
    seeds = [(v, mu, quantize_eps(eps, quantum), res)
             for (v, mu, eps), res in zip(seed, answers[n_global:])]
    return served, seeds


def check_served(index, g, served, seeds, what: str) -> None:
    """Every served answer vs ``query_batch`` on ``index``: global labels
    and cores equal, seed label/core/members equal the seed's row."""
    import numpy as np
    from repro.core import query_batch

    keys = sorted({k for k, _ in served} | {(mu, e) for _, mu, e, _ in seeds})
    want = {}
    for lo in range(0, len(keys), 32):
        chunk = keys[lo:lo + 32]
        chunk = chunk + [chunk[0]] * (32 - len(chunk))
        res = query_batch(index, g, np.asarray([k[0] for k in chunk],
                                               np.int32),
                          np.asarray([k[1] for k in chunk], np.float32))
        labels, cores = np.asarray(res.labels), np.asarray(res.is_core)
        for i, key in enumerate(chunk):
            want[key] = (labels[i], cores[i])
    bad = [k for k, r in served
           if not (np.array_equal(np.asarray(r.labels), want[k][0])
                   and np.array_equal(np.asarray(r.is_core), want[k][1]))]
    check(not bad, f"{what}: {len(served)} served global answers "
                   f"({len({k for k, _ in served})} distinct (mu, eps)) == "
                   f"query_batch" + (f"; differ at {bad[:4]}" if bad else ""))
    if not seeds:
        return
    ok = True
    for v, mu, e, r in seeds:
        labels, cores = want[(mu, e)]
        lab = int(labels[v])
        members = labels == lab if lab >= 0 else np.zeros(g.n, bool)
        ok &= (r.label == lab and r.is_core == bool(cores[v])
               and np.array_equal(r.member_mask, members))
    check(ok, f"{what}: {len(seeds)} served seed answers == their "
              f"query_batch rows")


def ref_lane_sample(g, plan, sims, ref_pol, per_group: int) -> None:
    """σ of every group on the ``ref`` lane, for up to ``per_group``
    evenly spaced edges of each, vs ``sims`` (built under the auto lane,
    the compiled kernel on a TPU) bit for bit."""
    import numpy as np

    eu = np.asarray(g.edge_u, np.int64)
    ev = np.asarray(g.nbrs, np.int64)
    _, _, keys = plan.route(eu, ev)
    picks = []
    for key in np.unique(keys):
        idx = np.flatnonzero(keys == key)
        take = np.linspace(0, len(idx) - 1, min(per_group, len(idx)))
        picks.append(idx[np.unique(take.astype(np.int64))])
    sel = np.concatenate(picks)
    ref = plan.edge_sims(eu[sel], ev[sel], np.asarray(g.wgts)[sel],
                         policy=ref_pol)
    check(np.array_equal(np.asarray(ref).view(np.uint32),
                         np.asarray(sims)[sel].view(np.uint32)),
          f"full sigma auto lane == ref lane on {len(sel)} edges sampled "
          f"from all {len(picks)} groups (bit-identical)")


def device_calls(engine) -> str:
    """Engine device calls so far: count and summed seconds (host clock,
    through the host copy of the answer) of global and seed calls."""
    hists = engine.registry.snapshot()["histograms"]
    parts = []
    for name in ("engine.device_call", "engine.seed_device_call"):
        h = hists.get(name, {"count": 0, "sum": 0.0})
        parts.append(f"{name} n={h['count']} sum={h['sum']:.2f}s")
    return ", ".join(parts)


def lane_counters(*registries) -> dict:
    out = {}
    for reg in registries:
        for k, v in reg.snapshot()["counters"].items():
            if k.startswith("backend.lane."):
                out[k] = out.get(k, 0) + v
    return out


def full_served_path(clock: Clock) -> None:
    import jax
    import numpy as np
    from repro.backend.policy import LANE_REF, default_policy
    from repro.core import ApproxParams, plan_for, power_law_graph
    from repro.core.lsh import simhash_sketches
    from repro.core.update import random_delta
    from repro.kernels import ops
    from repro.serve import EngineConfig, LiveIndexService

    with clock.phase("full.graph"):
        g = power_law_graph(**FULL_GRAPH)
    deg = np.diff(np.asarray(g.offsets))
    print(f"full graph: n={g.n} m={g.m} m2={g.m2} max_degree={deg.max()} "
          f"mean_degree={deg.mean():.2f}", flush=True)
    shutil.rmtree(CATALOG, ignore_errors=True)
    svc = LiveIndexService(str(CATALOG), config=EngineConfig(),
                           measure="cosine")
    with clock.phase("full.create (exact build, auto lanes + snapshot)"):
        svc.create("g", g)
    plan = plan_for(g)
    shapes = kernel_shapes(plan)
    print(f"full bucket_probe groups (P, T): edges = {shapes}", flush=True)
    check(bool(shapes), "full build routes groups to compiled bucket_probe")
    ref_pol = ref_policy()
    with clock.phase("full.sigma ref-lane sample"):
        ref_lane_sample(g, plan, svc.index("g").edge_sims, ref_pol,
                        REF_SAMPLE)
    del plan
    rng = np.random.default_rng(FULL_GRAPH["seed"])
    params = ApproxParams.parse(APPROX)

    async def run():
        async with svc:
            with clock.phase(f"full.serve ({N_GLOBAL} global + {N_SEED} "
                             f"seed)"):
                served, seeds = await _serve(svc, "g", rng, g.n, N_GLOBAL,
                                             N_SEED)
            print(device_calls(svc.engine), flush=True)
            with clock.phase("full.check served == query_batch"):
                check_served(svc.index("g"), g, served, seeds, "full")
            del served, seeds
            for i in range(DELTA_BATCHES):
                delta = random_delta(svc.graph("g"), DELTA_EDITS, rng)
                with clock.phase(f"full.apply delta {i + 1} "
                                 f"({DELTA_EDITS} edits)"):
                    info = await svc.apply("g", delta)
                print(f"delta {i + 1}: frontier={info.n_frontier} "
                      f"groups={info.n_sim_groups}", flush=True)
            g2 = svc.graph("g")
            with clock.phase(f"full.serve after deltas ({N_AFTER} global "
                             f"+ {N_AFTER} seed)"):
                after, after_seeds = await _serve(svc, "g", rng, g2.n,
                                                  N_AFTER, N_AFTER)
            print(device_calls(svc.engine), flush=True)
            # approximate-first ingest of the edited graph; its refine is
            # the from-scratch exact build the post-delta index must equal
            with clock.phase(f"full.register_approximate ({APPROX})"):
                svc.register_approximate("g_approx", g2, params=params)
            approx_sims = np.asarray(svc.index("g_approx").edge_sims)
            with clock.phase("full.sigma-hat ref lane (sketched edges)"):
                sk = simhash_sketches(g2, params.samples,
                                      jax.random.PRNGKey(params.seed))
                ref_hat = np.clip(np.asarray(
                    ops.simhash_edge_similarity_kernel(
                        sk, g2.edge_u, g2.nbrs, params.samples,
                        policy=ref_pol, lane=LANE_REF)), 0.0, 1.0)
                del sk
            cdeg = np.asarray(g2.closed_degrees())
            high = cdeg > params.samples
            sketched = high[np.asarray(g2.edge_u)] & high[np.asarray(g2.nbrs)]
            check(np.array_equal(approx_sims[sketched].view(np.uint32),
                                 ref_hat[sketched].view(np.uint32)),
                  f"approximate sigma-hat compiled lane == ref lane "
                  f"({int(sketched.sum())} sketched half-edges)")
            del ref_hat
            with clock.phase(f"full.serve approx ({N_AFTER} global)"):
                a_served, a_seeds = await _serve(svc, "g_approx", rng, g2.n,
                                                 N_AFTER, 0)
            print(device_calls(svc.engine), flush=True)
            with clock.phase("full.check approx"):
                check_served(svc.index("g_approx"), g2, a_served, a_seeds,
                             "approx")
            with clock.phase("full.refine (exact build in the worker)"):
                fp_refined = await svc.refine("g_approx")
            check(fp_refined == svc.fingerprint("g"),
                  "post-delta index fingerprint == from-scratch exact build "
                  "of the edited graph (the refine)")
            refined = svc.index("g_approx")
            check(np.array_equal(
                approx_sims[~sketched].view(np.uint32),
                np.asarray(refined.edge_sims)[~sketched].view(np.uint32)),
                f"approximate index's exact (section 6.3) edges == exact "
                f"sigma ({int((~sketched).sum())} half-edges)")
            with clock.phase("full.check after deltas"):
                check_served(refined, g2, after, after_seeds, "after deltas")
        return svc.stats()

    stats = asyncio.run(run())
    lanes = lane_counters(default_policy().registry, svc.engine.registry,
                          ref_pol.registry)
    print(f"lane counters: {json.dumps(lanes, sort_keys=True)}", flush=True)
    print(f"engine: requests={stats['requests']} "
          f"device_queries={stats['device_queries']} "
          f"seed_device_queries={stats['seed_device_queries']} "
          f"seed_spills={stats['seed_spills']} "
          f"jit_recompiles={stats['jit_recompiles']}", flush=True)
    check(lanes.get("backend.lane.bucket_probe.pallas-compiled", 0) > 0,
          "bucket_probe ran on the compiled lane")
    check(lanes.get("backend.lane.hamming.pallas-compiled", 0) > 0,
          "hamming ran on the compiled lane")
    check(not any(v for k, v in lanes.items()
                  if k.endswith(".pallas-interpret")),
          "no pallas-interpret dispatch")
    print(f"peak_bytes_in_use={peak_bytes()} (n={g.n})", flush=True)
    shutil.rmtree(CATALOG, ignore_errors=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def four_chips(clock: Clock, spec: dict) -> None:
    import numpy as np
    from repro.backend.policy import ExecutionPolicy
    from repro.obs import MetricsRegistry
    from repro.core import (build_index, compute_similarities,
                            power_law_graph, query_batch,
                            query_batch_sharded, query_mesh)
    from repro.core.distributed import sharded_edge_similarities
    from repro.serve import EngineConfig, MicroBatchEngine
    from repro.serve.cache import quantize_eps

    with clock.phase("graph"):
        g = power_law_graph(**spec)
    print(f"graph: n={g.n} m={g.m} m2={g.m2}", flush=True)
    mesh = query_mesh(4)
    with clock.phase("chip0.similarities + build"):
        s1 = compute_similarities(g)
        idx = build_index(g, sims=s1)
    mesh_pol = ExecutionPolicy(registry=MetricsRegistry())
    with clock.phase("mesh4.sharded_edge_similarities"):
        s4 = sharded_edge_similarities(g, mesh=mesh, policy=mesh_pol)
    print(f"mesh4 lane counters: "
          f"{json.dumps(lane_counters(mesh_pol.registry), sort_keys=True)}",
          flush=True)
    check(lane_counters(mesh_pol.registry).get(
        "backend.lane.bucket_probe.pallas-compiled", 0) > 0,
        "sharded sigma ran bucket_probe on the compiled lane")
    check(np.array_equal(np.asarray(s1).view(np.uint32),
                         np.asarray(s4).view(np.uint32)),
          f"sharded sigma on 4 chips == one-chip sigma ({g.m2} half-edges, "
          f"bit-identical)")
    cfg = EngineConfig(shards=4)
    keys = [(mu, quantize_eps(eps, cfg.eps_quantum)) for mu, eps in POOL[:32]]
    mus = np.asarray([k[0] for k in keys], np.int32)
    epss = np.asarray([k[1] for k in keys], np.float32)
    with clock.phase("chip0.query_batch"):
        r1 = query_batch(idx, g, mus, epss)
        l1, c1 = np.asarray(r1.labels), np.asarray(r1.is_core)
    with clock.phase("mesh4.query_batch_sharded"):
        r4 = query_batch_sharded(idx, g, mus, epss, mesh=mesh)
        l4, c4 = np.asarray(r4.labels), np.asarray(r4.is_core)
    check(np.array_equal(l1, l4) and np.array_equal(c1, c4)
          and np.array_equal(np.asarray(r1.n_clusters),
                             np.asarray(r4.n_clusters)),
          "query_batch_sharded on 4 chips == one-chip query_batch")
    engine = MicroBatchEngine(config=cfg)
    fp = engine.register(idx, g)
    rng = np.random.default_rng(spec["seed"])
    picks = rng.integers(len(keys), size=64)

    async def run():
        async with engine:
            return await asyncio.gather(
                *[engine.query(*keys[i], fingerprint=fp) for i in picks])

    with clock.phase("mesh4.engine shards=4 (64 global)"):
        answers = asyncio.run(run())
    check(all(np.array_equal(np.asarray(a.labels), l1[i])
              and np.array_equal(np.asarray(a.is_core), c1[i])
              for i, a in zip(picks, answers)),
          "EngineConfig(shards=4) answers == one-chip query_batch")
    print(f"engine: jit_recompiles="
          f"{engine.batch_stats()['jit_recompiles']}", flush=True)
    print(f"peak_bytes_in_use (device 0)={peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on a 4-chip mesh "
                    "against device 0")
    ap.add_argument("--small", action="store_true",
                    help="with --chips 4: compare on the 4,096-vertex "
                    "exactness graph instead of the 2^20-vertex one (the "
                    "full size costs ~5 minutes of the 4-chip host)")
    args = ap.parse_args()

    from repro.backend.policy import ENV_LANE, LANE_INTERPRET
    if os.environ.get(ENV_LANE) == LANE_INTERPRET:
        print(f"FAILED: {ENV_LANE}={LANE_INTERPRET} would run every kernel "
              "in the host interpreter; refusing to start", file=sys.stderr)
        return 2

    import jax
    from repro.backend.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"FAILED: JAX found no TPU (device 0 is {dev.platform!r}); "
              "this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"FAILED: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    clock = Clock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(clock, SMALL_GRAPH if args.small else FULL_GRAPH)
        else:
            small_exactness(clock)
            full_served_path(clock)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        clock.report_compiles()
    print(f"total {time.perf_counter() - t0:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
