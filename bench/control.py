#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

The configuration states float32 similarities; the control is the plain
reference computed one precision below (bfloat16), put in the program's
place, on the graph and the requests of a cell. Its readings of the
numbers a run compares must fail their limits, or the comparison could
not tell a program that computes in lower precision from a sound one.

    python bench/control.py --workload <cell> --seeds 1 2 3

prints one JSON line of readings per seed and exits 1 if any seed's
control passes every limit. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness, reference  # noqa: E402

LOWER = "bfloat16"


def as_program(ref: harness.RefIndex) -> tuple[dict, dict]:
    """A reference index in the shape of a served one."""
    idx = dict(ref.orders)
    idx["edge_sims"] = ref.sims
    return idx, {"offsets": ref.offsets, "nbrs": ref.nbrs}


def readings(workload: str, seed: int, rehearse: bool = False,
             spec: dict | None = None) -> dict:
    """The cell's compared numbers with the control in the program's
    place (the reference at the configuration's precision as the truth)."""
    if spec is None:
        from bench.run import load_spec
        spec = load_spec()
    cell = next(c for c in spec["workloads"] if c["name"] == workload)
    cfg = harness.load_json("configs", cell["config"])
    t = harness.load_json("traffic", cell["traffic"])
    rg = harness.make_graph(cfg, seed, rehearse)
    graph = rg.reference().relabel(rg.perm)
    rng = harness.rng_for(seed, "control")
    truth = harness.RefIndex.build(graph)
    control = harness.RefIndex.build(graph, LOWER)
    out = harness.compare_index(*as_program(control), truth)
    want, got = harness.Clusterings(truth), harness.Clusterings(control)
    if t["driver"] == "sweep":
        bad = 0
        for _ in range(t["sample_keys"]):
            mu = int(rng.integers(t["mu"][0], t["mu"][1] + 1))
            eps = harness.eps_of(int(rng.integers(
                round(t["eps"][0] / t["eps_grid"]),
                round(t["eps"][1] / t["eps_grid"]) + 1)), t["eps_grid"])
            labels, core = got(mu, eps)
            ans = type("A", (), {"labels": labels, "is_core": core})
            bad += harness.global_bad(ans, want(mu, eps))
        out["labels_bad"] = bad
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    caught = True
    for seed in args.seeds:
        got = readings(args.workload, seed, args.rehearse)
        fails = {k: v for k, v in got.items() if v > 0}
        caught &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "readings": got}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
