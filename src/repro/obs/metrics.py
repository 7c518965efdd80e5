"""Metrics primitives: counters, gauges, and log-bucketed histograms.

Dependency-free (stdlib only) so every layer — core kernels, the serve
stack, benchmarks, the CLI — can record into one
:class:`MetricsRegistry` without import cycles or optional extras.

Design constraints, in order:

* **hot-path cheap** — ``Histogram.record`` is a bisect over ~60 floats
  plus a few adds under a per-instance lock; ``Counter.inc`` is one add
  under a lock. Both are microseconds against device calls that take
  milliseconds, so the serve engine can record every request.
* **thread-safe** — the serve stack mutates metrics from the event loop
  *and* the engine's single-worker offload executor (``apply_delta``
  runs off-loop since PR 5). Every mutation takes the owning
  primitive's lock; plain ``dict[key] += 1`` (the old ``engine.stats``)
  is a lost-update bug under that split and is gone.
* **mergeable** — histograms with identical bucket edges add
  bucket-wise, so per-replica registries can aggregate into fleet-wide
  latency distributions (the ROADMAP's replica-fleet direction) and a
  benchmark can diff two snapshots to isolate one traffic wave
  (:func:`hist_delta`).
* **snapshot = JSON** — :meth:`MetricsRegistry.snapshot` returns plain
  dicts/lists/numbers; ``json.dumps`` round-trips it losslessly
  (:meth:`Histogram.from_snapshot`).

Buckets are **fixed log-spaced** bounds: ``buckets_per_decade`` buckets
per power of ten between ``lo`` and ``hi``, plus an underflow bucket
(values ≤ ``lo``, including 0) and an overflow bucket (values ≥ ``hi``).
Quantile estimates return the upper edge of the bucket holding the
``ceil(q·count)``-th smallest observation — the same rank the
``inverted_cdf`` order statistic uses — so the estimate is within one
(multiplicative) bucket width of the true order statistic by
construction.
"""
from __future__ import annotations

import bisect
import math
import threading
from typing import Dict, Iterable, List, Optional

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "hist_delta", "hist_quantile",
]


class Counter:
    """Monotone event count (thread-safe)."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, amount: int = 1) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Point-in-time level (queue depth, live index count); thread-safe.

    ``mode`` declares how this gauge folds across a fleet of registries
    (:meth:`MetricsRegistry.merge_snapshot`): ``"sum"`` for additive
    levels (queue depths add across replicas), ``"max"`` for watermarks
    (the fleet's replication staleness is its *worst* replica's lag —
    summing three replicas each 2 deltas behind into "6 behind" is a
    lie). The mode travels in snapshots so the aggregating side needs no
    out-of-band schema.
    """

    MODES = ("sum", "max")

    __slots__ = ("_lock", "_value", "_unset", "mode")

    def __init__(self, mode: str = "sum") -> None:
        if mode not in self.MODES:
            raise ValueError(f"gauge mode must be one of {self.MODES}, "
                             f"got {mode!r}")
        self._lock = threading.Lock()
        self._value = 0.0
        self._unset = True      # no reading yet: the 0.0 is no watermark
        self.mode = mode

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)
            self._unset = False

    def add(self, amount: float) -> None:
        with self._lock:
            self._value += amount
            self._unset = False

    def merge_value(self, value: float) -> None:
        """Fold one peer registry's reading in, per this gauge's mode.
        The first reading into an unset max-mode gauge is taken as is
        (an all-negative fleet keeps its negative watermark)."""
        with self._lock:
            if self.mode == "max" and not self._unset:
                self._value = max(self._value, float(value))
            elif self.mode == "max":
                self._value = float(value)
            else:
                self._value += float(value)
            self._unset = False

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed log-spaced-bucket latency histogram (thread-safe, mergeable).

    ``edges`` holds the **upper** edge of every finite bucket:
    ``edges[0] == lo`` closes the underflow bucket; the overflow bucket
    (values ≥ ``hi``) is the trailing ``counts`` slot with no finite
    edge. Two histograms merge iff their edges match exactly.
    """

    __slots__ = ("_lock", "edges", "counts", "count", "sum", "min", "max")

    def __init__(self, lo: float = 1e-6, hi: float = 100.0,
                 buckets_per_decade: int = 8, *,
                 _edges: Optional[List[float]] = None) -> None:
        if _edges is not None:
            self.edges = list(_edges)
        else:
            if not (0.0 < lo < hi):
                raise ValueError(f"need 0 < lo < hi, got ({lo}, {hi})")
            n_inner = int(math.ceil(
                (math.log10(hi) - math.log10(lo)) * buckets_per_decade))
            # exact endpoint replaces the last computed edge so hi itself
            # lands in the overflow bucket regardless of float rounding
            self.edges = [lo] + [
                lo * 10.0 ** (i / buckets_per_decade)
                for i in range(1, n_inner)] + [hi]
        if any(a >= b for a, b in zip(self.edges, self.edges[1:])):
            raise ValueError("bucket edges must be strictly increasing")
        self._lock = threading.Lock()
        self.counts = [0] * (len(self.edges) + 1)   # + overflow
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    # ------------------------------------------------------------------
    def bucket_index(self, value: float) -> int:
        """Index of the bucket ``value`` falls in (0 = underflow,
        ``len(edges)`` = overflow). Bucket *i* < overflow covers
        ``(edges[i-1], edges[i]]`` (underflow: ``(-inf, edges[0]]``)."""
        return bisect.bisect_left(self.edges, value)

    def record(self, value: float) -> None:
        value = float(value)
        i = self.bucket_index(value)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += value
            if self.min is None or value < self.min:
                self.min = value
            if self.max is None or value > self.max:
                self.max = value

    def merge(self, other: "Histogram") -> None:
        """Add ``other``'s observations into this histogram in place."""
        if self.edges != other.edges:
            raise ValueError("cannot merge histograms with different edges")
        with other._lock:
            counts = list(other.counts)
            count, total = other.count, other.sum
            omin, omax = other.min, other.max
        with self._lock:
            for i, c in enumerate(counts):
                self.counts[i] += c
            self.count += count
            self.sum += total
            if omin is not None and (self.min is None or omin < self.min):
                self.min = omin
            if omax is not None and (self.max is None or omax > self.max):
                self.max = omax

    # ------------------------------------------------------------------
    def quantile(self, q: float) -> float:
        """Upper edge of the bucket holding the ``ceil(q·count)``-th
        smallest observation (the ``inverted_cdf`` order-statistic rank).
        Underflow reports ``edges[0]``, overflow the observed max; an
        empty histogram reports 0.0."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            if self.count == 0:
                return 0.0
            rank = max(1, math.ceil(q * self.count))
            acc = 0
            for i, c in enumerate(self.counts):
                acc += c
                if acc >= rank:
                    if i >= len(self.edges):          # overflow bucket
                        return float(self.max)
                    return self.edges[i]
            return float(self.max)                     # unreachable

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "edges": list(self.edges),
                "counts": list(self.counts),
                "count": self.count,
                "sum": self.sum,
                "min": self.min,
                "max": self.max,
            }

    @classmethod
    def from_snapshot(cls, snap: dict) -> "Histogram":
        h = cls(_edges=snap["edges"])
        h.counts = list(snap["counts"])
        h.count = int(snap["count"])
        h.sum = float(snap["sum"])
        h.min = snap.get("min")
        h.max = snap.get("max")
        return h


def hist_quantile(snap: dict, q: float) -> float:
    """:meth:`Histogram.quantile` over a snapshot dict (no live object)."""
    return Histogram.from_snapshot(snap).quantile(q)


def hist_delta(now: dict, before: dict) -> dict:
    """Snapshot of the observations recorded *between* two snapshots of
    one histogram (``before`` taken earlier). Lets a benchmark isolate
    one traffic wave's latency distribution out of a cumulative
    histogram. ``min``/``max`` cannot be un-merged and report the
    interval-inclusive ``now`` values."""
    if now["edges"] != before["edges"]:
        raise ValueError("snapshots come from different histograms")
    return {
        "edges": list(now["edges"]),
        "counts": [a - b for a, b in zip(now["counts"], before["counts"])],
        "count": now["count"] - before["count"],
        "sum": now["sum"] - before["sum"],
        "min": now["min"],
        "max": now["max"],
    }


class MetricsRegistry:
    """Named counters, gauges, and histograms behind one lock-per-kind
    namespace. Get-or-create accessors make call sites one-liners::

        reg.inc("engine.requests")
        reg.observe("engine.e2e", dt)
        reg.gauge("engine.queue_depth").set(q.qsize())

    ``snapshot()`` is pure data (JSON-ready); ``merge_snapshot()`` folds
    another registry's snapshot in (counters/histograms add; gauges fold
    per their declared mode — sum for levels, max for watermarks).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}

    # ------------------------------------------------------------------
    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            return c

    def inc(self, name: str, amount: int = 1) -> None:
        self.counter(name).inc(amount)

    def gauge(self, name: str, mode: Optional[str] = None) -> Gauge:
        """Get-or-create a gauge. ``mode`` (``"sum"``/``"max"``) fixes
        the fleet-merge semantics at creation; re-access with a
        *different* explicit mode is a taxonomy bug and raises."""
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(mode or "sum")
            elif mode is not None and g.mode != mode:
                raise ValueError(
                    f"gauge {name!r} already registered with mode "
                    f"{g.mode!r}, not {mode!r}")
            return g

    def histogram(self, name: str, **kwargs) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(**kwargs)
            return h

    def observe(self, name: str, value: float) -> None:
        self.histogram(name).record(value)

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """All metrics as plain JSON-serializable data."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._hists)
        out = {
            "counters": {k: c.value for k, c in sorted(counters.items())},
            "gauges": {k: g.value for k, g in sorted(gauges.items())},
            "histograms": {k: h.snapshot() for k, h in sorted(hists.items())},
        }
        modes = {k: g.mode for k, g in sorted(gauges.items())
                 if g.mode != "sum"}
        if modes:   # only non-default modes travel (old snapshots: all sum)
            out["gauge_modes"] = modes
        return out

    def merge_snapshot(self, snap: dict) -> None:
        """Fold another registry's snapshot into this one (counters and
        histograms add; gauges fold per their merge mode — ``sum`` for
        additive levels like queue depth, ``max`` for watermarks like
        replication staleness). The incoming snapshot's ``gauge_modes``
        wins when this registry has not seen the gauge yet; snapshots
        predating modes merge as all-sum (the old behavior)."""
        for name, v in snap.get("counters", {}).items():
            self.inc(name, int(v))
        modes = snap.get("gauge_modes", {})
        for name, v in snap.get("gauges", {}).items():
            self.gauge(name, modes.get(name)).merge_value(float(v))
        for name, hsnap in snap.get("histograms", {}).items():
            with self._lock:
                h = self._hists.get(name)
                if h is None:
                    h = self._hists[name] = Histogram(
                        _edges=hsnap["edges"])
            h.merge(Histogram.from_snapshot(hsnap))

    def names(self) -> List[str]:
        with self._lock:
            return sorted({*self._counters, *self._gauges, *self._hists})
