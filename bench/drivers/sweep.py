"""Closed loop of analysts exploring (μ, ε) through
``LiveIndexService.query``.

Each of ``clients`` analysts starts at a uniform point of the grid and,
with ``think_s`` between an answer and the next request, steps: with probability ``move_eps`` ε moves by
``eps_step`` grid units (either way), with ``move_mu`` μ moves by one,
otherwise it jumps to a uniform point. Moves stay inside the grid. The
walks come from the traffic file's ``walk_seed``, so every run asks the
same questions of the same graph under its own vertex names. A
request's latency runs from its issue to its answer; every request issued
inside the window counts, the last ones answered after it closes.

The traffic file's ``report`` names the statistics published as the
cell's end-to-end metrics.
"""
from __future__ import annotations

import asyncio
import time

import jax
import numpy as np

from bench import harness, service


def _grid(t: dict):
    mu_lo, mu_hi = t["mu"]
    e_lo, e_hi = (round(x / t["eps_grid"]) for x in t["eps"])
    return mu_lo, mu_hi, e_lo, e_hi


async def setup(run):
    svc, g = await service.serve_graph(run)
    mu, eps = run.traffic["warm"]     # outside the traffic's grid
    with jax.profiler.TraceAnnotation("bench.warm"):
        await svc.query(service.NAME, mu, eps)
    return {"g": g}


async def window(run, state, t0: float, t_end: float) -> dict:
    t = run.traffic
    svc = run.svc
    mu_lo, mu_hi, e_lo, e_hi = _grid(t)
    rng = np.random.default_rng(t["walk_seed"])
    samples = []            # (mu, eps_units, latency_s, answer)
    failures = []

    async def analyst(i: int):
        r = np.random.default_rng(rng.integers(1 << 62))
        mu, k = int(r.integers(mu_lo, mu_hi + 1)), int(r.integers(e_lo,
                                                                   e_hi + 1))
        while time.perf_counter() < t_end:
            eps = harness.eps_of(k, t["eps_grid"])
            t_issue = time.perf_counter()
            try:
                ans = await svc.query(service.NAME, mu, eps)
            except Exception as e:  # noqa: BLE001 - counted as failed
                failures.append(repr(e))
            else:
                samples.append((mu, k, time.perf_counter() - t_issue, ans))
            u = r.random()
            if u < t["move_eps"]:
                step = int(r.integers(t["eps_step"][0], t["eps_step"][1] + 1))
                k = int(np.clip(k + step * r.choice((-1, 1)), e_lo, e_hi))
            elif u < t["move_eps"] + t["move_mu"]:
                mu = int(np.clip(mu + r.choice((-1, 1)), mu_lo, mu_hi))
            else:
                mu = int(r.integers(mu_lo, mu_hi + 1))
                k = int(r.integers(e_lo, e_hi + 1))
            if t["think_s"]:
                await asyncio.sleep(t["think_s"])

    with jax.profiler.TraceAnnotation("bench.window"):
        await asyncio.gather(*(analyst(i) for i in range(t["clients"])))
    lat = np.array([s[2] for s in samples])
    stats = harness.latency_stats(lat)
    run.log(f"sweep: {len(samples)} answered, {len(failures)} failed, "
            f"{len({(s[0], s[1]) for s in samples})} distinct (mu, eps); "
            f"latency s {harness.fmt_stats(stats)}")
    return {"attempted": len(samples) + len(failures),
            "failed": len(failures), "samples": samples,
            "metrics": harness.report(t, stats)}


async def collect(run, state, win) -> dict:
    """Host copies of what the check reads, before the service stops."""
    idx = harness.host_index(run.svc.index(service.NAME))
    return {"index": idx, "graph": harness.host_graph(state["g"])}


def check(run, kept: dict, win: dict) -> list:
    t = run.traffic
    ref = harness.RefIndex.build(run.graph.reference().relabel(
        run.graph.perm))
    out = harness.compare_index(kept["index"], kept["graph"], ref)
    keys = sorted({(s[0], s[1]) for s in win["samples"]})
    pick = run.rng("check").permutation(len(keys))[: t["sample_keys"]]
    chosen = {keys[i] for i in pick}
    answers = harness.Clusterings(ref)
    bad = checked = 0
    for mu, k, _, ans in win["samples"]:
        if (mu, k) in chosen:
            bad += harness.global_bad(ans, answers(mu, harness.eps_of(k, t["eps_grid"])))
            checked += 1
    run.log(f"checked {checked} answers at {len(chosen)} (mu, eps) drawn "
            f"from the seed")
    return [(k, v, 0) for k, v in out.items()] + [("labels_bad", bad, 0)]
