"""Back-to-back approximate-first ingest of new graphs through
``LiveIndexService.register_approximate``.

The loop of ``bench/drivers/build.py``, with the configuration's
``sketch`` (method, samples, sketch seed, degree heuristic) in place of
the exact build: each build takes the configuration's graph under a fresh
vertex permutation drawn from the seed (the edge lists are prepared in
set-up), ``from_edge_list`` runs inside the window, and builds run one
after another; the one running when the window closes finishes and
counts. The rate is undirected edges of every completed build over the
time from the window's start to the end of the last build. Set-up runs
one approximate build of its own, so every program the window runs is
compiled.

The check compares ``check_builds`` of the builds with
``bench/reference_lsh.py``, each number against the configuration's
``limits`` (0 where none is stated), and logs how far the reference's
approximate clustering is from its exact one at three (μ, ε) drawn from
the seed.
"""
from __future__ import annotations

import itertools
import time

import jax
import numpy as np

from bench import harness, reference_lsh, service


def params(config: dict):
    """The program's sketch parameters from the configuration."""
    from repro.core.approx import ApproxParams
    s = config["sketch"]
    return ApproxParams(method=s["method"], samples=int(s["samples"]),
                        seed=int(s["seed"]),
                        degree_heuristic=bool(s["degree_heuristic"]))


def register(svc, name: str, g, p) -> str:
    with jax.profiler.TraceAnnotation("bench.register_approximate"):
        return svc.register_approximate(name, g, params=p)


async def setup(run):
    t = run.traffic
    rg = run.graph = harness.make_graph(run.config, run.seed, run.rehearse)
    rng = run.rng("builds")
    perms = [rng.permutation(rg.n) for _ in range(t["prepared"] + 1)]
    lists = [rg.edges(perm=p, rng=rng) for p in perms]
    p = params(run.config)
    svc = run.svc = service.new_service()
    g = service.graph_from_edges(rg.n, lists[0])
    with jax.profiler.TraceAnnotation("bench.warm"):
        register(svc, "warm", g, p)
    await svc.__aenter__()
    return {"perms": perms[1:], "lists": lists[1:], "rng": rng, "params": p}


async def window(run, state, t0: float, t_end: float) -> dict:
    svc, p = run.svc, state["params"]
    rg, perms, lists = run.graph, state["perms"], state["lists"]
    builds = []               # (name, perm index, seconds, undirected m)
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in itertools.count():
            if time.perf_counter() >= t_end:
                break
            tb = time.perf_counter()
            if k == len(lists):
                perms.append(state["rng"].permutation(rg.n))
                lists.append(rg.edges(perm=perms[k], rng=state["rng"]))
            g = service.graph_from_edges(rg.n, lists[k])
            lists[k] = None
            register(svc, f"b{k}", g, p)
            builds.append((f"b{k}", k, time.perf_counter() - tb, g.m))
    t_last = time.perf_counter()
    secs = np.array([b[2] for b in builds])
    rate = sum(b[3] for b in builds) / (t_last - t0)
    run.log(f"approx_build: {len(builds)} builds of m={builds[0][3]} edges "
            f"({p.spec()}), seconds each "
            f"{harness.fmt_stats(harness.latency_stats(secs))}; "
            f"{rate:.6g} edges/s over {t_last - t0:.6g}s")
    return {"attempted": len(builds), "failed": 0, "builds": builds,
            "metrics": harness.report(run.traffic, {"edges_per_s": rate})}


async def collect(run, state, win) -> dict:
    """Host copies of the builds the check compares: ``check_builds`` of
    them, drawn from the seed (the last one always among them)."""
    builds = win["builds"]
    m2 = run.svc.graph(builds[-1][0]).m2
    win["extra"] = {"builds": len(builds), "m2": m2,
                    "sketch_words": -(-state["params"].samples // 32)}
    pick = run.rng("check").permutation(len(builds) - 1)
    chosen = [builds[-1]] + [builds[i] for i in
                             pick[: run.traffic["check_builds"] - 1]]
    kept = []
    for name, k, _, _ in chosen:
        kept.append((k, harness.host_index(run.svc.index(name)),
                     harness.host_graph(run.svc.graph(name))))
    return {"kept": kept, "perms": state["perms"]}


def check(run, kept: dict, win: dict) -> list:
    t0 = time.perf_counter()
    base = run.graph.reference()
    run.log(f"reference graph with triangles "
            f"({time.perf_counter() - t0:.1f}s)")
    sketch, limits = run.config["sketch"], run.config.get("limits", {})
    worst: dict = {}
    for i, (k, idx, g) in enumerate(kept["kept"]):
        t0 = time.perf_counter()
        ref = reference_lsh.RefApprox.build(base.relabel(kept["perms"][k]),
                                            sketch)
        got = reference_lsh.compare(idx, g, ref)
        run.log(f"build b{k}: {int(ref.sketched.sum())} of {len(ref.nbrs)} "
                f"half-edges sketched; {got} "
                f"({time.perf_counter() - t0:.1f}s)")
        for key, value in got.items():
            worst[key] = (max(worst.get(key, value), value)
                          if key in reference_lsh.WORST
                          else worst.get(key, 0) + value)
        if i == 0:
            rng = run.rng("agreement")
            for _ in range(3):
                mu = int(rng.integers(2, 17))
                eps = harness.eps_of(int(rng.integers(10, 91)), 0.01)
                t0 = time.perf_counter()
                agree = reference_lsh.agreement(ref, mu, eps)
                run.log(f"agreement of the reference's approximate and "
                        f"exact clusterings: {agree} "
                        f"({time.perf_counter() - t0:.1f}s)")
    run.log(f"checked {len(kept['kept'])} of {len(win['builds'])} builds")
    return [(key, value, limits.get(key, 0)) for key, value in worst.items()]
