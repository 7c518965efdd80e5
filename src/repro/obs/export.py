"""Export surfaces for a :class:`~repro.obs.metrics.MetricsRegistry`:
JSON files and a periodic one-line dump for long-running
``scan_serve serve``/``update`` processes.
"""
from __future__ import annotations

import asyncio
import json
from typing import Iterable, Optional

__all__ = ["write_json", "render_line", "dump_loop"]


def write_json(snapshot: dict, path: str) -> None:
    """Write a registry snapshot as indented JSON (CLI ``--metrics-json``,
    CI artifact)."""
    with open(path, "w") as f:
        json.dump(snapshot, f, indent=2)
        f.write("\n")


def render_line(snapshot: dict,
                quantiles: Iterable[float] = (0.5, 0.99)) -> str:
    """One compact status line per dump tick: every counter and gauge,
    plus count/quantiles of every histogram (units: milliseconds)."""
    from repro.obs.metrics import hist_quantile

    parts = []
    for name, v in snapshot.get("counters", {}).items():
        parts.append(f"{name}={v}")
    for name, v in snapshot.get("gauges", {}).items():
        parts.append(f"{name}={v:g}")
    for name, h in snapshot.get("histograms", {}).items():
        if not h["count"]:
            continue
        qs = "/".join(
            f"{hist_quantile(h, q) * 1e3:.2f}" for q in quantiles)
        tag = "/".join(f"p{int(q * 100)}" for q in quantiles)
        parts.append(f"{name}[n={h['count']},{tag}={qs}ms]")
    return "stats: " + " ".join(parts)


async def dump_loop(registry, interval_s: float,
                    emit=print, max_dumps: Optional[int] = None) -> None:
    """Periodically print a compact registry status line (the
    ``scan_serve stats``-style dump that runs alongside ``serve`` /
    ``update`` traffic). Cancel the task to stop it; ``max_dumps``
    bounds it for tests."""
    n = 0
    while max_dumps is None or n < max_dumps:
        await asyncio.sleep(interval_s)
        emit(render_line(registry.snapshot()))
        n += 1
