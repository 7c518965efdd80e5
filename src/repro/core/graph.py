"""Graph representation for the SCAN engine.

Graphs are stored in a jit-static padded CSR form:

  * ``offsets``  int32[n+1]  — row starts into the half-edge arrays.
  * ``nbrs``     int32[m2]   — neighbor vertex ids, each row sorted ascending.
  * ``wgts``     float32[m2] — edge weights (1.0 for unweighted graphs).
  * ``edge_u``   int32[m2]   — source vertex of each half-edge (CSR row id,
                               materialized so per-edge passes are gathers).

``m2 = 2m`` symmetric half-edges. Vertex ids are ``[0, n)`` (the paper uses
1-based ids; 0-based is the array-native choice). Graphs are simple:
no self-loops, no duplicate edges.

Everything downstream (similarity, index construction, queries, LSH) consumes
this structure with fixed shapes, which is what makes the whole SCAN engine
jit-able and shard_map-able.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Symmetric CSR graph. ``n``/``m2`` are static (python ints)."""

    offsets: jax.Array  # int32[n+1]
    nbrs: jax.Array     # int32[m2], row-sorted ascending
    wgts: jax.Array     # float32[m2]
    edge_u: jax.Array   # int32[m2]
    n: int = dataclasses.field(metadata=dict(static=True))
    m2: int = dataclasses.field(metadata=dict(static=True))

    @property
    def m(self) -> int:
        return self.m2 // 2

    def degrees(self) -> jax.Array:
        """Open-neighborhood degrees |N(v)|, int32[n]."""
        return jnp.diff(self.offsets)

    def closed_degrees(self) -> jax.Array:
        """Closed-neighborhood sizes |N̄(v)| = deg(v) + 1."""
        return self.degrees() + 1


def from_edge_list(
    n: int,
    edges: Sequence[Tuple[int, int]] | np.ndarray,
    weights: Optional[Sequence[float] | np.ndarray] = None,
) -> CSRGraph:
    """Build a CSRGraph from an undirected edge list (host-side).

    Deduplicates edges, drops self-loops, symmetrizes.
    """
    if n > 2 ** 31:
        # vertex ids must fit in 31 bits: the incremental-update path
        # (repro.core.update) merges edits on packed (u << 32 | v) int64
        # keys, which silently collide beyond that — refuse up front
        raise ValueError(
            f"n={n} exceeds 2**31: vertex ids must fit in 31 bits for the "
            "packed edit-merge keys used by incremental updates")
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if weights is None:
        weights = np.ones(len(edges), dtype=np.float32)
    weights = np.asarray(weights, dtype=np.float32)
    if len(weights) != len(edges):
        raise ValueError("weights length must match edges length")
    # canonicalize, drop self loops, dedup (keep first weight)
    keep = edges[:, 0] != edges[:, 1]
    edges, weights = edges[keep], weights[keep]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    key = lo * n + hi
    _, first = np.unique(key, return_index=True)
    lo, hi, weights = lo[first], hi[first], weights[first]

    u = np.concatenate([lo, hi])
    v = np.concatenate([hi, lo])
    w = np.concatenate([weights, weights])
    order = np.lexsort((v, u))
    u, v, w = u[order], v[order], w[order]

    offsets = np.zeros(n + 1, dtype=np.int32)
    np.add.at(offsets, u + 1, 1)
    offsets = np.cumsum(offsets, dtype=np.int64).astype(np.int32)
    return CSRGraph(
        offsets=jnp.asarray(offsets),
        nbrs=jnp.asarray(v.astype(np.int32)),
        wgts=jnp.asarray(w),
        edge_u=jnp.asarray(u.astype(np.int32)),
        n=int(n),
        m2=int(len(u)),
    )


def to_dense(g: CSRGraph, closed: bool = False, weighted: bool = True) -> jax.Array:
    """Dense adjacency float32[n, n]. ``closed`` adds the identity (w=1)."""
    a = jnp.zeros((g.n, g.n), dtype=jnp.float32)
    vals = g.wgts if weighted else jnp.ones_like(g.wgts)
    a = a.at[g.edge_u, g.nbrs].set(vals)
    if closed:
        a = a + jnp.eye(g.n, dtype=jnp.float32)
    return a


def edge_endpoints(g: CSRGraph) -> Tuple[jax.Array, jax.Array]:
    """(u, v) int32[m2] arrays of half-edge endpoints."""
    return g.edge_u, g.nbrs


def undirected_edge_mask(g: CSRGraph) -> jax.Array:
    """bool[m2], true for the canonical (u < v) copy of each edge."""
    return g.edge_u < g.nbrs


def random_graph(
    n: int,
    avg_degree: float,
    *,
    seed: int = 0,
    weighted: bool = False,
    planted_clusters: int = 0,
    p_in_over_p_out: float = 8.0,
) -> CSRGraph:
    """Synthetic test graphs (host-side numpy).

    ``planted_clusters > 0`` draws a planted-partition graph (useful for
    quality metrics — SCAN should recover the blocks); otherwise G(n, p).
    """
    rng = np.random.default_rng(seed)
    target_m = int(n * avg_degree / 2)
    if planted_clusters > 1:
        labels = rng.integers(0, planted_clusters, size=n)
        # sample within/between edges with ratio p_in_over_p_out
        frac_in = p_in_over_p_out / (p_in_over_p_out + 1.0)
        m_in = int(target_m * frac_in)
        m_out = target_m - m_in
        edges = []
        # within-cluster edges
        for _ in range(4):  # oversample, dedup later
            u = rng.integers(0, n, size=2 * m_in)
            shift = rng.integers(1, max(2, n // planted_clusters), size=2 * m_in)
            order = np.argsort(labels, kind="stable")
            pos = np.searchsorted(labels[order], labels[u])
            cnt = np.bincount(labels, minlength=planted_clusters)
            v = order[(pos + shift % np.maximum(cnt[labels[u]], 1))]
            ok = labels[v] == labels[u]
            edges.append(np.stack([u[ok], v[ok]], axis=1))
        e_in = np.concatenate(edges)[: 2 * m_in]
        u = rng.integers(0, n, size=2 * m_out)
        v = rng.integers(0, n, size=2 * m_out)
        e_out = np.stack([u, v], axis=1)
        e = np.concatenate([e_in, e_out])
    else:
        u = rng.integers(0, n, size=3 * target_m)
        v = rng.integers(0, n, size=3 * target_m)
        e = np.stack([u, v], axis=1)
    e = e[e[:, 0] != e[:, 1]][: 2 * target_m]
    w = rng.uniform(0.1, 1.0, size=len(e)).astype(np.float32) if weighted else None
    return from_edge_list(n, e, w)


def power_law_graph(
    n: int,
    alpha: float = 2.1,
    *,
    avg_degree: float = 8.0,
    seed: int = 0,
    weighted: bool = False,
    hub_degree: int = 0,
    max_degree: int = 0,
) -> CSRGraph:
    """Chung–Lu-style power-law graph (host-side numpy).

    Vertex attachment weights follow w_i ∝ (i+1)^(-1/(α-1)) — the expected
    degree sequence of a power-law graph with exponent α — and edge
    endpoints are drawn ∝ w. ``hub_degree > 0`` additionally wires vertex 0
    to that many distinct random vertices, forcing one hub with
    deg ≫ median (the skew case the degree-bucketed similarity engine
    exists for; a dense-padded layout would pay O(n·hub_degree)).

    ``max_degree > 0`` caps every vertex's expected degree there (the
    excess weight spreads over the uncapped vertices): uncapped, α = 2.1
    at n = 2²⁰ gives vertex 0 an expected degree in the hundreds of
    thousands, far past what social graphs of that size show.

    ``n·avg_degree`` endpoint pairs are drawn before self-loops and
    duplicates drop, so the mean degree comes out at up to
    ``2·avg_degree`` (less where skew makes many duplicates).
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-1.0 / (alpha - 1.0))
    p = w / w.sum()
    target_m = int(n * avg_degree / 2)
    if max_degree > 0:
        # expected degree of i is 2 · (2·target_m draws) · p_i
        cap = max_degree / (4.0 * target_m)
        if cap * n <= 1.0:
            raise ValueError(f"max_degree={max_degree} is below the mean "
                             "degree: no capped weights exist")
        while p.max() > cap * (1 + 1e-9):
            p = np.minimum(p, cap)
            p = p / p.sum()
    u = rng.choice(n, size=3 * target_m, p=p)
    v = rng.choice(n, size=3 * target_m, p=p)
    e = np.stack([u, v], axis=1)
    e = e[e[:, 0] != e[:, 1]][: 2 * target_m]
    if hub_degree > 0:
        others = rng.permutation(np.arange(1, n))[: min(hub_degree, n - 1)]
        hub_e = np.stack([np.zeros(len(others), np.int64), others], axis=1)
        e = np.concatenate([e, hub_e])
    wgt = (rng.uniform(0.1, 1.0, size=len(e)).astype(np.float32)
           if weighted else None)
    return from_edge_list(n, e, wgt)


def hub_ring_graph(
    n: int,
    hub_degree: int,
    *,
    seed: int = 0,
    weighted: bool = False,
) -> CSRGraph:
    """Star-with-ring: vertex 0 is a hub wired to ``hub_degree`` spokes,
    all other vertices form a ring (so every non-hub degree is 2–3 while
    the hub dominates — the adversarial case for any global-width padded
    layout: Δ = hub_degree, median degree ≈ 2).
    """
    rng = np.random.default_rng(seed)
    ring = np.stack([np.arange(1, n), np.concatenate(
        [np.arange(2, n), [1]])], axis=1)
    spokes = rng.permutation(np.arange(1, n))[: min(hub_degree, n - 1)]
    star = np.stack([np.zeros(len(spokes), np.int64), spokes], axis=1)
    e = np.concatenate([ring, star])
    w = (rng.uniform(0.1, 1.0, size=len(e)).astype(np.float32)
         if weighted else None)
    return from_edge_list(n, e, w)


def graph_from_dense(a: np.ndarray, weighted: bool = True) -> CSRGraph:
    """Build from a dense symmetric adjacency (testing convenience)."""
    a = np.asarray(a)
    n = a.shape[0]
    iu, iv = np.nonzero(np.triu(a, k=1))
    w = a[iu, iv].astype(np.float32) if weighted else None
    return from_edge_list(n, np.stack([iu, iv], axis=1), w)
