"""Back-to-back exact ingest of new graphs through
``LiveIndexService.create``.

Each build takes the configuration's graph under a fresh vertex
permutation drawn from the seed (the edge lists are prepared in set-up),
so no per-graph cache and no index fingerprint ever sees a graph twice;
``from_edge_list`` runs inside the window. Builds run one after another;
the one running when the window closes finishes and counts. The rate is
undirected edges of every completed build over the time from the window's
start to the end of the last build. Set-up runs one build of its own, so
every program the window runs is compiled. Should the window outrun the
prepared lists, further ones are drawn in it, their cost counted.
"""
from __future__ import annotations

import itertools
import time

import jax
import numpy as np

from bench import harness, service


async def setup(run):
    t = run.traffic
    rg = run.graph = harness.make_graph(run.config, run.seed, run.rehearse)
    rng = run.rng("builds")
    perms = [rng.permutation(rg.n) for _ in range(t["prepared"] + 1)]
    lists = [rg.edges(perm=p, rng=rng) for p in perms]
    svc = run.svc = service.new_service()
    g = service.graph_from_edges(rg.n, lists[0])
    with jax.profiler.TraceAnnotation("bench.warm"):
        service.create(svc, "warm", g)
    await svc.__aenter__()
    return {"perms": perms[1:], "lists": lists[1:], "rng": rng}


async def window(run, state, t0: float, t_end: float) -> dict:
    svc = run.svc
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
            service.create(svc, f"b{k}", g)
            builds.append((f"b{k}", k, time.perf_counter() - tb, g.m))
    t_last = time.perf_counter()
    secs = np.array([b[2] for b in builds])
    rate = sum(b[3] for b in builds) / (t_last - t0)
    run.log(f"build: {len(builds)} builds of m={builds[0][3]} edges, "
            f"seconds each {harness.fmt_stats(harness.latency_stats(secs))}; "
            f"{rate:.6g} edges/s over {t_last - t0:.6g}s")
    return {"attempted": len(builds), "failed": 0, "builds": builds,
            "metrics": harness.report(run.traffic, {"edges_per_s": rate})}


async def collect(run, state, win) -> dict:
    """Host copies of the builds the check compares: ``check_builds`` of
    them, drawn from the seed (the last one always among them)."""
    builds = win["builds"]
    win["extra"] = {"builds": len(builds)}
    pick = run.rng("check").permutation(len(builds) - 1)
    chosen = [builds[-1]] + [builds[i] for i in
                             pick[: run.traffic["check_builds"] - 1]]
    kept = []
    for name, k, _, _ in chosen:
        kept.append((k, harness.host_index(run.svc.index(name)),
                     harness.host_graph(run.svc.graph(name))))
    return {"kept": kept, "perms": state["perms"]}


def check(run, kept: dict, win: dict) -> list:
    base = run.graph.reference()
    total = {"graph_bad": 0, "sigma_bad": 0, "order_bad": 0}
    for k, idx, g in kept["kept"]:
        ref = harness.RefIndex.build(base.relabel(kept["perms"][k]))
        for key, value in harness.compare_index(idx, g, ref).items():
            total[key] += value
    run.log(f"checked {len(kept['kept'])} of {len(win['builds'])} builds")
    return [(k, v, 0) for k, v in total.items()]
