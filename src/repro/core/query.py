"""Cluster queries against the SCAN index (paper §4.2, Algorithms 3–5).

Given (μ, ε):
  1. cores         — prefix of CO[μ] with θ ≥ ε              (Algorithm 3)
  2. similar edges — per-row NO prefixes with σ ≥ ε on cores (Alg. 5 line 4)
  3. clusters      — connectivity over core–core ε-similar edges (line 6)
  4. borders       — non-core neighbors attach to an ε-similar core
                     (Algorithm 4; deterministic variant of §7.3.4:
                      most-similar core, ties to the lower core id)

Steps 3 and 4 reduce over each vertex's CSR row (a segmented scan), never
scatter: see :mod:`repro.core.connectivity`.

The whole query is a single jit with (μ, ε) as traced scalars — one compiled
artifact answers every parameter setting, which is the point of the index.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.connectivity import (RowLayout, connected_components,
                                     row_layout, row_reduce)
from repro.core.graph import CSRGraph
from repro.core.index import ScanIndex, get_cores


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ClusterResult:
    labels: jax.Array      # int32[n]; component id (min core vertex id) or -1
    is_core: jax.Array     # bool[n]
    n_clusters: jax.Array  # int32 scalar
    # propagation rounds the connectivity loop ran for this answer (int32
    # scalar; per lane under query_batch); None where not computed here
    rounds: Optional[jax.Array] = None


def twin_slots(g: CSRGraph, max_row: int) -> jax.Array:
    """int32[m2]: the slot of each half-edge's reverse, (u, v) → (v, u).

    A lower-bound search for u in v's ascending row, branchless over a
    fixed step count (``max_row`` bounds the degree)."""
    eu, ev = g.edge_u, g.nbrs
    if g.m2 == 0:
        return jnp.zeros((0,), jnp.int32)
    top = g.m2 - 1

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        right = (lo < hi) & (ev[jnp.clip(mid, 0, top)] < eu)
        return (jnp.where(right, mid + 1, lo),
                jnp.where(right | (lo >= hi), hi, mid))

    lo, _ = jax.lax.fori_loop(0, max(int(max_row), 1).bit_length(), body,
                              (g.offsets[ev], g.offsets[ev + 1]))
    return lo


def cluster_labels(n: int, is_core, eu, ev, esim, esim_t, eps,
                   rows: RowLayout, emask=None, axis_name=None):
    """Steps 2–4 of a query from the core mask → (labels, n_clusters,
    rounds), ``rounds`` the connectivity loop's propagation rounds.

    Every per-vertex reduction runs over the vertex's own CSR row. For
    the border step slot (v, u) stands for half-edge (u, v), whose σ is
    ``esim_t`` (σ of the reverse slot): a non-core v takes the most
    ε-similar core neighbor, ties to the lower core id. With
    ``axis_name`` the slot arrays are one shard (``emask`` marks the real
    ones) and each reduction is merged across the axis."""
    core_u = is_core[eu]
    core_v = is_core[ev]
    sim_ok = esim >= eps
    sim_t_ok = esim_t >= eps
    if emask is not None:
        sim_ok = sim_ok & emask
        sim_t_ok = sim_t_ok & emask
    # ε-similar core–core half-edges → connectivity
    labels0, rounds = connected_components(
        n, ev, sim_ok & core_u & core_v, rows, vertex_mask=is_core,
        axis_name=axis_name)
    labels = jnp.where(is_core, labels0, jnp.int32(-1))

    # ---- border assignment (Algorithm 4, deterministic variant) ----
    with jax.named_scope("query.borders"):
        border = sim_t_ok & core_v & ~core_u  # row v non-core, nbr u core
        neg = jnp.float32(-1.0)
        best_sim = row_reduce(jnp.where(border, esim_t, neg), rows,
                              jnp.maximum, neg)
        if axis_name is not None:
            best_sim = jax.lax.pmax(best_sim, axis_name)
        best_row = best_sim[eu]
        tie = border & (esim_t >= best_row) & (best_row > neg)
        big = jnp.int32(n)
        best_core = row_reduce(jnp.where(tie, ev, big), rows, jnp.minimum,
                               big)
        if axis_name is not None:
            best_core = jax.lax.pmin(best_core, axis_name)
        has_border = best_core < big
        border_label = labels0[jnp.clip(best_core, 0, n - 1)]
        labels = jnp.where(~is_core & has_border, border_label, labels)

    # count distinct clusters = number of cores that are their own label
    n_clusters = jnp.sum(is_core & (labels == jnp.arange(n)))
    return labels, n_clusters, rounds


@functools.partial(jax.jit, static_argnames=())
def query(index: ScanIndex, g: CSRGraph, mu, eps) -> ClusterResult:
    """SCAN clustering for parameters (μ, ε) from the index."""
    mu = jnp.asarray(mu, jnp.int32)
    eps = jnp.asarray(eps, jnp.float32)
    max_row = index.max_cdeg - 1
    # graph-only layouts: under query_batch's vmap they stay unbatched
    rows = row_layout(g.offsets, g.edge_u, max_row)
    esim_t = index.edge_sims[twin_slots(g, max_row)]

    with jax.named_scope("query.cores"):
        is_core = get_cores(index, mu, eps)
    labels, n_clusters, rounds = cluster_labels(
        index.n, is_core, g.edge_u, g.nbrs, index.edge_sims, esim_t, eps,
        rows)
    return ClusterResult(labels=labels, is_core=is_core,
                         n_clusters=n_clusters, rounds=rounds)


@functools.partial(jax.jit, static_argnames=())
def query_batch(index: ScanIndex, g: CSRGraph, mus, epss) -> ClusterResult:
    """Answer a whole batch of (μ, ε) settings in one compiled call.

    ``mus`` int32[B] / ``epss`` float32[B] → ClusterResult with a leading
    batch axis (labels int32[B, n], is_core bool[B, n], n_clusters int32[B]).

    Because ``query`` treats (μ, ε) as traced scalars over a fixed index,
    vmapping over them shares one compiled artifact across the batch — the
    index arrays are closed over (broadcast), only the parameters vary.
    The inner connectivity ``while_loop`` runs until every batch member has
    converged; min-label propagation is monotone so already-converged
    members are fixed points and extra rounds are no-ops. ``rounds``
    int32[B] holds each member's own round count; the call ran their max.
    """
    mus = jnp.atleast_1d(jnp.asarray(mus, jnp.int32))
    epss = jnp.atleast_1d(jnp.asarray(epss, jnp.float32))
    return jax.vmap(lambda m, e: query(index, g, m, e))(mus, epss)


@jax.jit
def hubs_outliers(g: CSRGraph, labels: jax.Array):
    """Classify unclustered vertices (paper §4.3).

    hub     — neighbors in ≥ 2 distinct clusters
    outlier — unclustered, neighbors in ≤ 1 cluster
    Returns (is_hub bool[n], is_outlier bool[n]).
    """
    n = labels.shape[0]
    nbr_label = labels[g.nbrs]
    valid = nbr_label >= 0
    big = jnp.int32(n)
    lo = (
        jnp.full((n,), big).at[g.edge_u].min(jnp.where(valid, nbr_label, big))
    )
    hi = (
        jnp.full((n,), jnp.int32(-1))
        .at[g.edge_u]
        .max(jnp.where(valid, nbr_label, jnp.int32(-1)))
    )
    unclustered = labels < 0
    is_hub = unclustered & (hi > lo) & (hi >= 0)
    is_outlier = unclustered & ~is_hub
    return is_hub, is_outlier
