"""The system under test, as the drivers reach it: the graph handed over
as an edge list, and ``LiveIndexService`` over one catalog directory.

Every call the benchmark makes into the program goes through here or
through the service object this returns.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import harness

NAME = "g"


def graph_from_edges(n: int, edges: np.ndarray):
    """The program's CSR graph from an undirected edge list."""
    from repro.core.graph import from_edge_list
    with jax.profiler.TraceAnnotation("bench.from_edge_list"):
        return from_edge_list(n, edges)


def new_service():
    """A ``LiveIndexService`` with the default engine over a fresh
    catalog directory inside the checkout."""
    from repro.serve import EngineConfig, LiveIndexService
    return LiveIndexService(str(harness.fresh_dir(harness.CATALOG)),
                            config=EngineConfig(), measure="cosine")


def create(svc, name: str, g) -> str:
    """Exact build, snapshot and registration of ``g`` under ``name``."""
    with jax.profiler.TraceAnnotation("bench.create"):
        return svc.create(name, g)


async def serve_graph(run):
    """Set-up shared by the cells that query one live graph: the graph
    from the seed, the service, one exact build, the engine started.
    → (svc, g)."""
    rg = run.graph = harness.make_graph(run.config, run.seed, run.rehearse)
    edges = rg.edges(rng=run.rng("edge-order"))
    g = graph_from_edges(rg.n, edges)
    svc = run.svc = new_service()
    create(svc, NAME, g)
    await svc.__aenter__()
    return svc, g
