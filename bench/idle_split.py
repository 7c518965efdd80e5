#!/usr/bin/env python3
"""The device's idle time in a traced window, split by the program span
the host was in.

    python3 bench/idle_split.py [trace_dir]    # default: .bench_out/trace

Reads the newest profiler trace under ``trace_dir`` (``bench/run.py
--trace 1`` leaves its window's trace there), and gives every idle
stretch of the window, all of them and not only the longest, to the
innermost host event covering it whose name matches ``PROGRAM_SPANS``:
the program's ``repro.obs`` spans, which annotate the profiler under
their own names, and the harness's ``bench.*`` annotations other than
the window's own. Prints one JSON line: the window and idle seconds
(averaged over the devices that ran anything), the number of span
events found, idle seconds per span name, longest first, and
``outside_spans``, the idle time no such span covers. Planes and the
window are found by ``trace_patterns.json``.
"""
from __future__ import annotations

import glob
import heapq
import json
import re
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402

PROGRAM_SPANS = r"^(bench|live|index|similarity|store|engine|query)\."
OUTSIDE = "outside_spans"


def idle_before(t: np.ndarray, gs: np.ndarray,
                ge: np.ndarray) -> np.ndarray:
    """Idle time in [gs[0], t) for each ``t``, given disjoint sorted idle
    intervals [gs, ge)."""
    done = np.concatenate([[0.0], np.cumsum(ge - gs)])
    k = np.searchsorted(ge, t, side="right")      # gaps ended by t
    nxt = gs[np.minimum(k, len(gs) - 1)]        # first gap still open
    part = np.where(k < len(gs), np.clip(t - nxt, 0.0, None), 0.0)
    return done[k] + part


def split_idle(gaps: list, spans: list, lo: float, hi: float) -> dict:
    """Idle time per innermost covering span. ``gaps``: disjoint (start,
    end) idle intervals; ``spans``: (name, start, end) host events. Each
    instant of [lo, hi) belongs to the shortest span covering it, or to
    ``OUTSIDE``; the result sums the idle time of each owner."""
    gaps = sorted(gaps)
    gs = np.array([g[0] for g in gaps], np.float64)
    ge = np.array([g[1] for g in gaps], np.float64)
    spans = [(n, max(a, lo), min(b, hi)) for n, a, b in spans]
    spans = [s for s in spans if s[2] > s[1]]
    cuts = np.unique(np.array([lo, hi] + [x for _, a, b in spans
                                          for x in (a, b)], np.float64))
    idle = (np.diff(idle_before(cuts, gs, ge)) if len(gs)
            else np.zeros(len(cuts) - 1))
    opens = sorted(range(len(spans)), key=lambda i: spans[i][1])
    out: dict = {}
    active: list = []          # heap of (duration, end, span index)
    j = 0
    for c, a in enumerate(cuts[:-1]):
        while j < len(opens) and spans[opens[j]][1] <= a:
            i = opens[j]
            heapq.heappush(active, (spans[i][2] - spans[i][1],
                                    spans[i][2], i))
            j += 1
        while active and active[0][1] <= a:   # ended: drop lazily
            heapq.heappop(active)
        owner = spans[active[0][2]][0] if active else OUTSIDE
        if idle[c]:
            out[owner] = out.get(owner, 0.0) + float(idle[c])
    return out


def split_file(path: str, patterns: dict,
               program_spans: str = PROGRAM_SPANS) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    dev_re = re.compile(patterns["device_plane"])
    span_re = re.compile(program_spans)
    devices, spans, window = [], [], None
    for plane in pd.planes:
        if dev_re.search(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = (lines.get(patterns["op_line"])
                    or lines.get(patterns["module_line"]))
            if line is not None:
                ops = [(float(ev.start_ns), float(ev.start_ns)
                        + float(ev.duration_ns)) for ev in line.events]
                if ops:
                    devices.append(ops)
        elif plane.name.startswith(patterns["host_plane"]):
            for line in plane.lines:
                for ev in line.events:
                    a = float(ev.start_ns)
                    b = a + float(ev.duration_ns)
                    if ev.name == patterns["window"]:
                        window = (a, b)
                    elif span_re.search(ev.name):
                        spans.append((ev.name, a, b))
    if window is None:
        raise ValueError(f"no {patterns['window']!r} annotation in {path}")
    lo, hi = window
    split: dict = {}
    idle_ns = 0.0
    for ops in devices:
        busy, gaps = trace_reduce.union_length(
            [o[0] for o in ops], [o[1] for o in ops], lo, hi)
        idle_ns += (hi - lo) - busy
        for name, ns in split_idle(gaps, spans, lo, hi).items():
            split[name] = split.get(name, 0.0) + ns
    ndev = max(len(devices), 1)
    by_span = sorted(((n, ns / ndev * 1e-9) for n, ns in split.items()),
                     key=lambda x: -x[1])
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle_ns / ndev * 1e-9,
            "spans": len(spans), "idle_by_span": dict(by_span)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    directory = argv[0] if argv else str(harness.TRACES)
    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not files:
        print(f"no trace under {directory}", file=sys.stderr)
        return 1
    print(json.dumps(split_file(files[-1], trace_reduce.load_patterns())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
