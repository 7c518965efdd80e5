"""From a profiler trace of the window to the numbers the per-layer
readers and the result's ``device``/``breakdown`` keys report.

* busy: the union of the intervals in which an operation ran on a device,
  inside the window (the harness's ``bench.window`` annotation), averaged
  over the devices that ran anything;
* device time per program: the summed durations of the program
  executions whose name matches a pattern of ``trace_patterns.json``, and
  how many executions there were; per kernel likewise over operations;
* idle gaps: the stretches of the window with no operation on the device,
  each named by the innermost host event (the harness's annotations, and
  JAX's own) that covers its middle.

Every name the reduction looks for is in ``trace_patterns.json``, kept
with the benchmark, so that a renamed program changes one line of data.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path

import numpy as np

PATTERNS = Path(__file__).resolve().parent / "trace_patterns.json"


def load_patterns(path: Path = PATTERNS) -> dict:
    with open(path) as f:
        return json.load(f)


def options():
    """Profiler options for a window's trace: host annotations kept, no
    Python call tracing (which would slow the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def union_length(starts: np.ndarray, ends: np.ndarray, lo: float,
                 hi: float) -> tuple[float, list]:
    """Length of the union of [start, end) clipped to [lo, hi), and the
    gaps of that union inside [lo, hi) as (start, end) pairs."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    edge = lo
    for a, b in zip(s, e):
        if cur_e is None or a > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            if a > edge:
                gaps.append((edge, a))
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
        edge = max(edge, cur_e)
    if cur_e is not None:
        busy += cur_e - cur_s
    if hi > edge:
        gaps.append((edge, hi))
    return busy, gaps


def _base(name: str) -> str:
    """A program's name without the execution's id suffix."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    programs: dict      # pattern key → (seconds, executions), per device
    kernels: dict       # pattern key → (seconds, operations), per device
    top_programs: list  # [(name, seconds)] over all programs, per device
    gaps: list          # [(host activity, seconds)], longest first

    def device_time(self, key: str):
        """(seconds, executions) of a program pattern; None if no
        execution matched."""
        sec, count = self.programs.get(key, (0.0, 0))
        return (sec, count) if count else None

    def kernel_time(self, key: str):
        sec, count = self.kernels.get(key, (0.0, 0))
        return (sec, count) if count else None

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_programs[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns)
             + float(ev.duration_ns)) for ev in line.events]


def reduce_file(path: str, patterns: dict) -> Reduced:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    dev_re = re.compile(patterns["device_plane"])
    host_events = []
    window = None
    devices = []
    for plane in pd.planes:
        if dev_re.search(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            mods = lines.get(patterns["module_line"], [])
            ops = lines.get(patterns["op_line"], mods)
            if ops or mods:
                devices.append((mods, ops))
        elif plane.name.startswith(patterns["host_plane"]):
            for line in plane.lines:
                for ev in _events(line):
                    host_events.append(ev)
                    if ev[0] == patterns["window"]:
                        window = (ev[1], ev[2])
    if window is None:
        raise ValueError(f"no {patterns['window']!r} annotation in {path}")
    lo, hi = window
    busy_total, programs, kernels, tops = 0.0, {}, {}, {}
    all_gaps = []
    for mods, ops in devices:
        starts = np.array([o[1] for o in ops])
        ends = np.array([o[2] for o in ops])
        busy, gaps = union_length(starts, ends, lo, hi)
        busy_total += busy
        all_gaps.extend(gaps)
        inside = [m for m in mods if m[2] > lo and m[1] < hi]
        for key, pat in patterns["programs"].items():
            rx = re.compile(pat)
            hit = [m for m in inside if rx.search(m[0])]
            sec, cnt = programs.get(key, (0.0, 0))
            programs[key] = (sec + sum(m[2] - m[1] for m in hit) * 1e-9,
                             cnt + len(hit))
        for key, pat in patterns["kernels"].items():
            rx = re.compile(pat)
            hit = [o for o in ops if o[2] > lo and o[1] < hi
                   and rx.search(o[0])]
            sec, cnt = kernels.get(key, (0.0, 0))
            kernels[key] = (sec + sum(o[2] - o[1] for o in hit) * 1e-9,
                            cnt + len(hit))
        for m in inside:
            name = _base(m[0])
            tops[name] = tops.get(name, 0.0) + (m[2] - m[1]) * 1e-9
    ndev = max(len(devices), 1)
    programs = {k: (s / ndev, c) for k, (s, c) in programs.items()}
    kernels = {k: (s / ndev, c) for k, (s, c) in kernels.items()}
    top = sorted(((n, s / ndev) for n, s in tops.items()),
                 key=lambda x: -x[1])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total / ndev * 1e-9,
                   programs=programs, kernels=kernels,
                   top_programs=top,
                   gaps=name_gaps(all_gaps, host_events,
                                  patterns["window"]))


def name_gaps(gaps: list, host_events: list, window: str,
              keep: int = 10) -> list:
    """The ``keep`` longest gaps, each named by the shortest host event
    that covers its middle (other than the window's own annotation)."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:keep]
    if host_events:
        starts = np.array([h[1] for h in host_events])
        ends = np.array([h[2] for h in host_events])
    out = []
    for a, b in longest:
        mid = 0.5 * (a + b)
        name = "no host event"
        if host_events:
            cover = np.flatnonzero((starts <= mid) & (ends >= mid))
            cover = [i for i in cover if host_events[i][0] != window]
            if cover:
                i = min(cover, key=lambda i: ends[i] - starts[i])
                name = host_events[i][0]
        out.append((name, (b - a) * 1e-9))
    return out


def reduce_dir(directory, patterns: dict) -> Reduced:
    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not files:
        raise ValueError(f"no trace under {directory}")
    return reduce_file(files[-1], patterns)
