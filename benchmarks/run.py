"""Benchmark entry point — one section per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [section ...] \
           [--json out.json] [--shards K]
Sections: fig5 fig6 approx serve update roofline (default: all; ``approx``
subsumes the old fig8/fig9 aliases and commits ``BENCH_approx.json``).
Output: ``name,us_per_call,derived`` CSV lines on stdout; ``--json`` also
writes the same rows as structured JSON (the artifact CI uploads per run,
so regressions are diffable across commits). ``--shards K`` forces K host
platform devices *before* jax initializes, so the sharded-query rows in
the ``serve`` section run on a real K-way mesh:

    XLA-free shorthand for
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.run serve      ==      --shards 8
"""
from __future__ import annotations

import argparse
import json
import sys

SECTIONS = ("fig5", "fig6", "approx", "serve", "update", "roofline")
# the approx section subsumes the paper's fig8 (construction) and fig9/10
# (quality) and commits the combined BENCH_approx.json snapshot
ALIASES = {"fig7": "fig6", "fig8": "approx", "fig9": "approx",
           "fig10": "approx"}


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sections", nargs="*", default=list(SECTIONS),
                    help=f"subset of {SECTIONS} (default: all)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write rows as JSON (CI artifact)")
    ap.add_argument("--shards", type=int, default=0,
                    help="force K host devices for the sharded serve rows")
    return ap.parse_args(argv)


def _parse_line(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    row = {"name": name, "us_per_call": float(us)}
    for kv in filter(None, derived.split(";")):
        if "=" in kv:
            k, v = kv.split("=", 1)
            num = v[:-1] if v.endswith("x") else v   # speedup=3.42x → 3.42
            try:
                row[k] = (float(num) if "." in num or "e" in num.lower()
                          else int(num))
            except ValueError:
                row[k] = v
    return row


def main() -> None:
    args = parse_args(sys.argv[1:])
    sections = [ALIASES.get(s, s) for s in args.sections]
    unknown = set(sections) - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections: {sorted(unknown)}")
    if args.shards > 1:
        if set(sections) != {"serve"}:
            # splitting the host into K emulated devices throttles every
            # section's intra-op threading; numbers from other sections
            # would be silently non-comparable with unsharded runs
            raise SystemExit(
                "--shards only applies to the serve section; run "
                "`benchmarks.run serve --shards K` (other sections would "
                "be silently measured on K-way-split host compute)")
        # must land before jax's backend initializes (first device query)
        from repro.core.distributed import force_host_devices
        force_host_devices(args.shards)

    from repro.backend.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    lines: list[str] = []
    if "fig5" in sections:
        from benchmarks import bench_index_construction
        lines += bench_index_construction.run()
    if "fig6" in sections:
        from benchmarks import bench_query
        lines += bench_query.run()
    if "approx" in sections:
        from benchmarks import bench_approx
        lines += bench_approx.run()
    if "serve" in sections:
        from benchmarks import bench_serve
        lines += bench_serve.run()
    if "update" in sections:
        from benchmarks import bench_update
        lines += bench_update.run()
    if "roofline" in sections:
        from benchmarks import roofline
        lines += roofline.run()

    if args.json:
        rows = [_parse_line(ln) for ln in lines]
        meta = {"sections": sections, "shards": args.shards}
        with open(args.json, "w") as f:
            json.dump({"meta": meta, "rows": rows}, f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
