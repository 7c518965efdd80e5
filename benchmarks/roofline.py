"""Roofline tables (EXPERIMENTS.md §Roofline).

Two sections:

* the dry-run table: reads experiments/dryrun/<mesh>/*.json and prints the
  three terms (compute / memory / collective, seconds), the dominant term,
  MODEL_FLOPS, the useful-compute ratio, and the roofline fraction;
* the similarity-pass table: achieved vs peak for the bucketed similarity
  engine on live suite graphs. Bytes and flops are modeled from the
  SimilarityPlan's group shapes — per half-edge the kernel gathers a
  pe-wide probe row and a te-wide target row (ids + weights, 8 bytes per
  element) and runs pe binary searches over te targets plus the σ
  multiply-accumulate epilogue. Peaks come from :data:`PEAKS`, the
  published per-chip numbers keyed by jax's ``device_kind``; a device
  that is not in the table is an error, never a default.
"""
from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np


def load(mesh_dir: str):
    recs = []
    for path in sorted(glob.glob(os.path.join(mesh_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def fmt_ms(s):
    return f"{s * 1e3:.2f}" if s is not None else "-"


def table(recs, *, only_baseline=True):
    rows = []
    header = ("| arch | shape | status | compute ms | memory ms | coll ms | "
              "dominant | MODEL_GF/dev | useful | roofline frac |")
    sep = "|" + "---|" * 10
    rows.append(header)
    rows.append(sep)
    for r in recs:
        if r["status"] == "skip":
            rows.append(f"| {r['arch']} | {r['shape']} | SKIP ({r['reason'][:40]}…) "
                        "| - | - | - | - | - | - | - |")
            continue
        if r["status"] == "error":
            rows.append(f"| {r['arch']} | {r['shape']} | ERROR | - | - | - | "
                        "- | - | - | - |")
            continue
        t = r["roofline"]["terms_s"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | ok "
            f"| {fmt_ms(t['compute_s'])} | {fmt_ms(t['memory_s'])} "
            f"| {fmt_ms(t['collective_s'])} | {r['roofline']['dominant'].replace('_s','')} "
            f"| {r['roofline']['model_flops_per_device'] / 1e9:.1f} "
            f"| {r['roofline']['useful_ratio']:.3f} "
            f"| {r['roofline']['roofline_fraction']:.4f} |")
    return "\n".join(rows)


# published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
PEAKS = {
    "TPU v5 lite": {"gflops": 197_000.0, "gbps": 819.0,
                    "source": "Google Cloud TPU v5e (bf16 peak, HBM)"},
}


def device_peaks(kind: str | None = None) -> dict:
    """The :data:`PEAKS` row of ``kind`` (default: the first jax device).
    An unlisted device raises: a roofline share against guessed peaks
    would be a made-up number."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add a row with "
            f"its source to benchmarks/roofline.PEAKS (known: {sorted(PEAKS)})")
    return PEAKS[kind]


SIM_GRAPHS = ("sparse-8k", "powerlaw-8k")


def sim_pass_model(plan, eu, ev):
    """(bytes, flops) one bucketed similarity pass moves/executes, modeled
    from the plan's per-edge group shapes: pe = probe tiles^ × probe class
    width, te likewise for the target side."""
    from repro.backend.padding import np_pow2ceil

    pu, pv, _ = plan.route(np.asarray(eu, np.int64), np.asarray(ev, np.int64))
    widths = np.asarray(plan.widths, np.int64)
    pe = np_pow2ceil(plan.vtiles[pu]).astype(np.int64) * \
        widths[plan.vclass[pu]]
    te = np_pow2ceil(plan.vtiles[pv]).astype(np.int64) * \
        widths[plan.vclass[pv]]
    # ids (int32) + weights (f32) for both rows
    model_bytes = int(8 * (pe + te).sum())
    # pe binary searches of depth log2(te) + the 2·pe dot-product MACs
    compares = (pe * np.ceil(np.log2(np.maximum(te, 2)))).sum()
    flops = int(compares + 2 * pe.sum())
    return model_bytes, flops


def similarity_section():
    from benchmarks.common import load_graph, timeit
    from repro.core import compute_similarities
    from repro.core.similarity import plan_for
    from repro.backend.policy import default_policy

    lines = []
    pol = default_policy()
    peaks = device_peaks()
    print(f"\n### similarity pass (platform {pol.platform()}, "
          f"peaks {peaks['gflops']:.0f} GFLOP/s / {peaks['gbps']:.0f} GB/s, "
          f"{peaks['source']})\n")
    print("| graph | lane | m | GB | GFLOP | ms | GB/s | GFLOP/s "
          "| AI F/B | dominant | frac |")
    print("|" + "---|" * 11)
    for gname in SIM_GRAPHS:
        g = load_graph(gname)
        plan = plan_for(g)
        model_bytes, flops = sim_pass_model(
            plan, np.asarray(g.edge_u), np.asarray(g.nbrs))
        t = timeit(lambda: compute_similarities(g, "cosine"), trials=2)
        widest = int(np.asarray(plan.widths, np.int64).max())
        lane = pol.lane("bucket_probe", width=widest)
        gbps = model_bytes / t / 1e9
        gflops = flops / t / 1e9
        t_mem = model_bytes / (peaks["gbps"] * 1e9)
        t_cmp = flops / (peaks["gflops"] * 1e9)
        dominant = "memory" if t_mem >= t_cmp else "compute"
        frac = max(t_mem, t_cmp) / t
        print(f"| {gname} | {lane} | {g.m} | {model_bytes / 1e9:.3f} "
              f"| {flops / 1e9:.3f} | {t * 1e3:.1f} | {gbps:.2f} "
              f"| {gflops:.2f} | {flops / model_bytes:.2f} | {dominant} "
              f"| {frac:.4f} |")
        lines.append(
            f"roofline/simpass/{gname},{t * 1e6:.1f},"
            f"lane={lane};m={g.m};model_gb={model_bytes / 1e9:.3f};"
            f"model_gflop={flops / 1e9:.3f};achieved_gbps={gbps:.2f};"
            f"achieved_gflops={gflops:.2f};dominant={dominant};"
            f"roofline_frac={frac:.4f}")
    return lines


def run(out_dir: str = "experiments/dryrun"):
    lines = similarity_section()
    for mesh in ("pod16x16", "pod2x16x16"):
        d = os.path.join(out_dir, mesh)
        if not os.path.isdir(d):
            continue
        recs = [r for r in load(d)
                if "__" in os.path.basename(r.get("arch", "") or "x")
                or True]
        # keep only untagged baseline artifacts
        base = [r for r in recs if r.get("status")]
        print(f"\n### mesh {mesh} ({len(base)} cells)\n")
        print(table(base))
        ok = [r for r in base if r["status"] == "ok"]
        for r in ok:
            lines.append(
                f"roofline/{mesh}/{r['arch']}/{r['shape']},"
                f"{max(r['roofline']['terms_s'].values()) * 1e6:.1f},"
                f"dominant={r['roofline']['dominant']};"
                f"frac={r['roofline']['roofline_fraction']:.4f}")
    for l in lines:
        print(l)
    return lines


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else "experiments/dryrun")
