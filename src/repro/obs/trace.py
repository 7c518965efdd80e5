"""Structured trace spans: what happened, when, inside what.

A :class:`Tracer` hands out ``span()`` context managers. Each finished
span becomes one structured event — name, monotonic start time, duration,
attributes, and parent/child linkage — appended to a bounded ring buffer,
and (when the tracer owns a registry) its duration is recorded into the
histogram of the same name, so *every span taxonomy is automatically a
latency histogram taxonomy*: ``live.apply_delta`` the span and
``live.apply_delta`` the histogram are the same measurements.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
for its lifetime, so a profiler trace carries the program's spans on its
host plane, on the clock its device events use (its attributes ride
along as event stats while a trace is recording). With no trace
recording the annotation is an inactive ``TraceMe``, well under a
microsecond. ``event()`` records durations measured after the fact and
reaches the ring and the histograms only.

Nesting is tracked with a :mod:`contextvars` variable, so concurrent
asyncio tasks each see their own span stack (a span opened in task A is
never the parent of a span opened in task B). Plain
``loop.run_in_executor`` does **not** carry context into worker threads —
callers that offload work and want the worker's spans parented under the
caller's span must ship the context explicitly
(``contextvars.copy_context().run(fn)``), which is exactly what
``MicroBatchEngine.run_offloaded`` does for the apply pipeline.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from collections import deque
from typing import Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "maybe_span"]

# the innermost open span of the current task/thread (contextvar: each
# asyncio task and each thread sees its own chain)
_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_obs_current_span", default=None)


class Span:
    """One timed, attributed region. ``set(key=value, ...)`` attaches
    attributes any time before the ``with`` block exits."""

    __slots__ = ("name", "span_id", "parent_id", "t_start", "attrs")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 t_start: float, attrs: dict) -> None:
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t_start = t_start
        self.attrs = attrs

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self


class Tracer:
    """Span factory + bounded event buffer, optionally metric-backed.

    ``max_events`` bounds memory: the buffer is a ring, old events fall
    off. The histograms in the registry keep the *aggregate* view
    forever; the ring keeps the recent *structured* view for debugging.
    """

    def __init__(self, registry=None, max_events: int = 2048) -> None:
        self.registry = registry
        self._events: deque = deque(maxlen=max_events)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        parent = _CURRENT.get()
        sp = Span(name=name, span_id=next(self._ids),
                  parent_id=parent.span_id if parent is not None else None,
                  t_start=time.monotonic(), attrs=dict(attrs))
        token = _CURRENT.set(sp)
        # attributes reach the profiler only while it records: encoding
        # them is the annotation's one cost that is not constant
        ann = (TraceAnnotation(name, **attrs)
               if attrs and TraceAnnotation.is_enabled()
               else TraceAnnotation(name))
        try:
            with ann:
                yield sp
        finally:
            _CURRENT.reset(token)
            self._finish(sp, time.monotonic() - sp.t_start)

    def event(self, name: str, duration_s: float, *,
              t_start: Optional[float] = None, **attrs) -> None:
        """Record a span-shaped event for a duration measured elsewhere
        (e.g. queue wait, derived from an enqueue timestamp after the
        fact — there is no ``with`` block to wrap)."""
        parent = _CURRENT.get()
        sp = Span(name=name, span_id=next(self._ids),
                  parent_id=parent.span_id if parent is not None else None,
                  t_start=(time.monotonic() - duration_s
                           if t_start is None else t_start),
                  attrs=dict(attrs))
        self._finish(sp, duration_s)

    def inc(self, name: str, amount: int = 1) -> None:
        """Add to the registry counter ``name`` (no-op without a
        registry): lets a traced code path count work through the one
        tracer it is handed."""
        if self.registry is not None:
            self.registry.inc(name, amount)

    def _finish(self, sp: Span, duration_s: float) -> None:
        self._events.append({
            "name": sp.name,
            "span_id": sp.span_id,
            "parent_id": sp.parent_id,
            "t_start": sp.t_start,
            "duration_s": duration_s,
            "attrs": dict(sp.attrs),
        })
        if self.registry is not None:
            self.registry.observe(sp.name, duration_s)

    # ------------------------------------------------------------------
    def events(self, name: Optional[str] = None) -> List[dict]:
        """Snapshot of buffered events, oldest first (filtered by name)."""
        evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        return evs


def maybe_span(tracer: Optional[Tracer], name: str, **attrs):
    """``tracer.span(name, **attrs)``, or a context that records nothing
    when ``tracer`` is None (core paths are traced only on request)."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **attrs)
