#!/usr/bin/env python3
"""The controls of the sketched configuration's comparison.

Each control is a plainly wrong program put in the program's place on
one build's graph of a cell, and must fail the check named beside it, or
the comparison could not tell that fault from a sound program:

* ``bfloat16``: the reference's sketches with every partial sum rounded
  to bfloat16, one precision below the configuration's float32; it must
  fail ``sketch_h_off``;
* ``samples64``: σ̂ from the first 64 of the 256 sketch bits,
  cos(π·h₆₄/64), on the same sketched edges; it must fail
  ``theta_tail_share``, the check that holds σ̂ to its sample count.

    python bench/control_lsh.py --workload <cell> --seeds 1 2 3

prints one JSON line of readings per seed and control (the graph of the
run's first window build) and exits 1 if any control passes the check
it must fail. ``--controls`` picks some of them.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness, reference_lsh  # noqa: E402


def bfloat16(graph, sketch, truth):
    return reference_lsh.RefApprox.build(graph, sketch, "bfloat16")


def samples64(graph, sketch, truth):
    """``truth`` with h counted over its first 64 sketch bits and σ̂ read
    from the 64-sample table."""
    h = reference_lsh.popcount(truth.xor[:, :8])
    return dataclasses.replace(truth, h=h, samples=64)


CONTROLS = {"bfloat16": (bfloat16, "sketch_h_off"),
            "samples64": (samples64, "theta_tail_share")}


def as_program(ref: reference_lsh.RefApprox) -> tuple[dict, dict]:
    """A reference's σ̂ and its orders in the shape of a served index."""
    from bench import reference

    sims = ref.served_sims()
    idx = reference.orders(ref.offsets, ref.edge_u, ref.nbrs, sims)
    idx["edge_sims"] = sims
    return idx, {"offsets": ref.offsets, "nbrs": ref.nbrs}


def readings(workload: str, seed: int, rehearse: bool = False,
             spec: dict | None = None,
             controls=tuple(CONTROLS)) -> dict:
    """Per control, the cell's compared numbers with the control in the
    program's place, each beside its limit."""
    if spec is None:
        from bench.run import load_spec
        spec = load_spec()
    cell = next(c for c in spec["workloads"] if c["name"] == workload)
    cfg = harness.load_json("configs", cell["config"])
    rg = harness.make_graph(cfg, seed, rehearse)
    perm = harness.rng_for(seed, "builds").permutation(rg.n)
    graph = rg.reference().relabel(perm)
    truth = reference_lsh.RefApprox.build(graph, cfg["sketch"])
    limits = cfg.get("limits", {})
    out = {}
    for name in controls:
        make, _ = CONTROLS[name]
        got = reference_lsh.compare(
            *as_program(make(graph, cfg["sketch"], truth)), truth)
        out[name] = {k: {"value": v, "limit": limits.get(k, 0)}
                     for k, v in got.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", choices=sorted(CONTROLS),
                    default=sorted(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    caught = True
    for seed in args.seeds:
        got = readings(args.workload, seed, args.rehearse,
                       controls=args.controls)
        for name, checks in got.items():
            must = checks[CONTROLS[name][1]]
            caught &= must["value"] > must["limit"]
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": name,
                              "device": jax.devices()[0].device_kind,
                              "readings": checks}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
