#!/usr/bin/env python3
"""One run of one cell of the SCAN-service benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``: the graph and its guarantees) and a
traffic mix (``bench/traffic/<traffic>.json``: parameters read by the load
driver it names, ``bench/drivers/<driver>.py``). A run generates the graph
from the seed, brings the service up through ``LiveIndexService``, warms
the shapes its traffic uses (set-up), offers the traffic for ``--seconds``
(the window), then compares what the timed path produced with the plain
reference (``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports its per-layer metrics, each
read by ``bench/layers/<metric>.py``. Earlier lines carry counts and
diagnostics; the last line of standard output is the result:

    {"correct": true, "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

and the last lines of standard error repeat each compared number beside
its limit. Without a TPU (or with fewer chips than the cell asks for) the
run exits 3 and prints no result. ``--rehearse`` runs the cell at the
configuration's tiny rehearsal size on whatever device JAX has, for the
benchmark's own tests; its metric names carry a ``rehearsal.`` prefix so
that its numbers never pass for a cell's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.harness import BenchError  # noqa: E402

REHEARSAL_PREFIX = "rehearsal."


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {path}")
    with open(path) as f:
        return json.load(f)


def cell_of(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchError(f"unknown workload {workload!r}")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_for(spec: dict, cell: str) -> list:
    return [m for m in spec["end_to_end"] if reports(m, cell)]


def per_layer_for(spec: dict, cell: str) -> list:
    e2e = {m["name"] for m in end_to_end_for(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


class Run:
    """One run's settings and the state the driver and readers share."""

    def __init__(self, args, spec: dict, base: Path = harness.BENCH):
        self.args = args
        self.cell = cell_of(spec, args.workload)
        self.config = harness.load_json("configs", self.cell["config"], base)
        self.traffic = harness.load_json("traffic", self.cell["traffic"],
                                         base)
        self.driver = harness.load_module("drivers",
                                          self.traffic["driver"], base)
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearse = bool(args.rehearse)
        self.log = log
        self.clock = None
        self.svc = None           # the LiveIndexService, once up
        self.setup_s = None

    def rng(self, stream: str):
        return harness.rng_for(self.seed, stream)

    def registries(self) -> dict:
        """The program's registries this run reads: the engine's and the
        process-default execution policy's (lane counters of builds)."""
        from repro.backend.policy import default_policy
        regs = {"policy": default_policy().registry}
        if self.svc is not None:
            regs["engine"] = self.svc.engine.registry
        return regs

    def snapshot(self) -> dict:
        return {k: r.snapshot() for k, r in self.registries().items()}


def delta(before: dict, after: dict) -> dict:
    """Counters and histogram (count, sum) recorded between two
    snapshots, merged over registries."""
    counters, hists = {}, {}
    for reg, snap in after.items():
        old = before.get(reg, {"counters": {}, "histograms": {}})
        for k, v in snap["counters"].items():
            counters[k] = counters.get(k, 0) + v - old["counters"].get(k, 0)
        for k, h in snap["histograms"].items():
            o = old["histograms"].get(k, {"count": 0, "sum": 0.0})
            c, s = hists.get(k, (0, 0.0))
            hists[k] = (c + h["count"] - o["count"], s + h["sum"] - o["sum"])
    return {"counters": counters, "histograms": hists}


class Readings:
    """What a per-layer reader may read: registry deltas over the window,
    the reduced trace, and the driver's own counts."""

    def __init__(self, device_kind: str, window: dict, trace, extra: dict):
        self.device_kind = device_kind
        self.counters = window["counters"]
        self.histograms = window["histograms"]
        self.trace = trace
        self.extra = extra

    def counter(self, name: str) -> int:
        return self.counters.get(name, 0)

    def mean(self, name: str):
        """Mean of a histogram over the window; None without samples."""
        count, total = self.histograms.get(name, (0, 0.0))
        return total / count if count else None


def check_lines(checks: list) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}


async def drive(run: Run) -> dict:
    import jax

    from bench import trace_reduce

    driver = run.driver
    state = await driver.setup(run)
    tracing = bool(run.args.trace)
    if tracing:
        trace_dir = harness.fresh_dir(harness.TRACES)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_reduce.options())
    snap0 = run.snapshot()
    comp0 = run.clock.read()
    t_w0 = time.perf_counter()
    run.setup_s = t_w0 - T_START
    win = await driver.window(run, state, t_w0, t_w0 + run.seconds)
    t_w1 = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    comp1 = run.clock.read()
    snap1 = run.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {t_w1 - t_w0:.3f}s, compiles in window "
        f"{comp1[0] - comp0[0]} ({comp1[1] - comp0[1]:.3f}s), "
        f"peak_bytes_in_use {peak}")
    kept = await driver.collect(run, state, win)
    if run.svc is not None:
        await run.svc.__aexit__(None, None, None)
    run.svc = None
    del state
    gc.collect()
    reduced = None
    if tracing:
        reduced = trace_reduce.reduce_dir(harness.TRACES,
                                          trace_reduce.load_patterns())
    return {"win": win, "kept": kept, "snap": delta(snap0, snap1),
            "peak": peak, "trace": reduced, "window_s": t_w1 - t_w0,
            "compiles": comp1[0] - comp0[0]}


def execute(args, spec: dict | None = None, base: Path = harness.BENCH,
            ) -> tuple[dict, list]:
    """One run → (result line, compared numbers). Raises BenchError where
    no result may be printed."""
    import jax

    spec = spec if spec is not None else load_spec()
    run = Run(args, spec, base)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}")
    devices = jax.devices()
    if not run.rehearse:
        if devices[0].platform != "tpu":
            raise BenchError(f"JAX found no TPU (device 0 is "
                             f"{devices[0].platform!r})")
        if len(devices) < int(run.cell["chips"]):
            raise BenchError(f"the cell needs {run.cell['chips']} chips, "
                             f"JAX sees {len(devices)}")
        jax.config.update("jax_compilation_cache_dir",
                          str(harness.COMPILE_CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.clock = harness.CompileClock()
    out = asyncio.run(drive(run))
    win = out["win"]
    checks = run.driver.check(run, out["kept"], win)
    checks.append(("unanswered", int(win["failed"]), 0))
    correct = all(value <= limit for _, value, limit in checks)

    prefix = REHEARSAL_PREFIX if run.rehearse else ""
    metrics = {}
    if not args.trace:
        for m in end_to_end_for(spec, run.cell["name"]):
            value = (run.setup_s if m["name"] == "setup_s"
                     else win["metrics"].get(m["name"]))
            if value is not None:
                metrics[prefix + m["name"]] = {"value": value,
                                               "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak"]}
    breakdown = None
    if args.trace:
        trace = out["trace"]
        readings = Readings(dev.device_kind, out["snap"], trace,
                            win.get("extra", {}))
        for m in per_layer_for(spec, run.cell["name"]):
            reader = harness.load_module("layers", m["name"], base)
            value = reader.read(readings)
            if value is not None:
                metrics[prefix + m["name"]] = {"value": value,
                                               "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        breakdown = trace.breakdown()
    log(f"counters over the window: "
        f"{json.dumps(out['snap']['counters'], sort_keys=True)}")
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = check_lines(checks)
    return result, checks


def parse(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rehearsal size on any device (tests only)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks = execute(args)
    except BenchError as e:
        log(f"FAILED: {e}")
        return 3
    print(json.dumps(result), flush=True)
    for name, value, limit in checks:
        log(f"check {name}: {value} (limit {limit})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
