"""Observability: metrics registry, latency histograms, trace spans.

Telemetry for the serving stack and the index build. The paper
sells *query latency under many parameter settings*; this package is how
the repo measures that claim instead of asserting it:

  * :mod:`repro.obs.metrics` — :class:`MetricsRegistry` holding
    thread-safe counters, gauges, and fixed log-spaced-bucket latency
    :class:`Histogram`\\ s (mergeable across replicas, diffable across
    snapshots, JSON round-trippable);
  * :mod:`repro.obs.trace`   — :class:`Tracer` whose ``span()`` context
    manager emits structured events (monotonic timestamps, parent/child
    nesting via contextvars) *and* feeds the same-named registry
    histogram, so the span taxonomy is the latency taxonomy, and
    annotates the JAX profiler's host plane under the same name, so a
    device trace shows which span the host was in;
  * :mod:`repro.obs.export`  — JSON snapshot writer and the periodic
    one-line stats dump used by ``scan_serve serve``/``update``.

The serve wiring (span names + attributes per layer) is documented in
ROADMAP.md § Observability.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               hist_delta, hist_quantile)
from repro.obs.trace import Span, Tracer, maybe_span
from repro.obs.export import dump_loop, render_line, write_json

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "hist_delta", "hist_quantile",
    "Span", "Tracer", "maybe_span",
    "dump_loop", "render_line", "write_json",
]
