"""Sequential reference SCAN — the correctness oracle.

A direct, readable numpy/python transcription of the original SCAN
definitions (paper §3.1): per-edge similarity by explicit set intersection,
core determination, BFS structural-reachability clustering, deterministic
border attachment (most-similar core, ties to lower id — matching §7.3.4),
hub/outlier classification. O(m·Δ) time — test-scale only.

Every parallel-path test asserts exact agreement against this module.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Tuple

import numpy as np

from repro.core.graph import CSRGraph


def _neigh(g, off, nbrs, v):
    return nbrs[off[v]: off[v + 1]]


def similarities_ref(g: CSRGraph, measure: str = "cosine", *,
                     float32: bool = False) -> np.ndarray:
    """σ per half-edge (graph order), via explicit closed-set intersection.

    Sums are taken in float64. ``float32=True`` then forms the cosine in
    float32, the arithmetic the program states, ``dot / (‖u‖ · ‖v‖)``
    with ``‖v‖ = sqrt(Σ w²)``: each operation is rounded to float32, so
    on an unweighted graph (integer sums) the result is the program's σ
    bit for bit; by default the quotient is rounded once, at the end."""
    off = np.asarray(g.offsets)
    nbrs = np.asarray(g.nbrs)
    wgts = np.asarray(g.wgts)
    eu = np.asarray(g.edge_u)
    n = g.n

    wmap: Dict[Tuple[int, int], float] = {}
    for i in range(g.m2):
        wmap[(int(eu[i]), int(nbrs[i]))] = float(wgts[i])

    def wfun(a, b):  # weight of N̄(a) at element b; w(a,a)=1
        return 1.0 if a == b else wmap[(a, b)]

    norms = np.zeros(n)
    for v in range(n):
        s = 1.0 + sum(wmap[(v, int(x))] ** 2 for x in _neigh(g, off, nbrs, v))
        norms[v] = np.sqrt(np.float32(s)) if float32 else np.sqrt(s)

    sims = np.zeros(g.m2, dtype=np.float64)
    for i in range(g.m2):
        u, v = int(eu[i]), int(nbrs[i])
        nu = set(map(int, _neigh(g, off, nbrs, u))) | {u}
        nv = set(map(int, _neigh(g, off, nbrs, v))) | {v}
        shared = nu & nv
        if measure == "cosine":
            dot = sum(wfun(u, x) * wfun(v, x) for x in shared)
            if float32:
                sims[i] = np.float32(dot) / (np.float32(norms[u])
                                             * np.float32(norms[v]))
            else:
                sims[i] = dot / (norms[u] * norms[v])
        elif measure == "jaccard":
            sims[i] = len(shared) / len(nu | nv)
        else:
            raise ValueError(measure)
    return sims.astype(np.float32)


def simhash_similarities_ref(g: CSRGraph, samples: int, key,
                             degree_heuristic: bool = True) -> np.ndarray:
    """σ̂ per half-edge (graph order) of SimHash with ``samples`` bits
    under the §6.3 degree heuristic, float32.

    Bit j of v's sketch is ``Σ_{x∈N̄(v)} w(v,x)·R[x,j] ≥ 0``, with
    ``R[:, 32i:32i+32] = normal(fold_in(key, i), (n, 32))`` in float32, the
    documented derivation of the program's projections (the data, as
    weights would be); the sums are taken in float64 over those float32
    values, neighbour by neighbour in CSR order. h(u,v) =
    popcount(sketch(u) XOR sketch(v)) by Python integers, and σ̂ =
    max(0, cos(π·h/k)) in float32 where both endpoints have closed degree
    > k (every edge without the heuristic); every other edge keeps its
    exact float32 σ (:func:`similarities_ref` with ``float32=True``).
    """
    import jax

    off = np.asarray(g.offsets)
    nbrs = np.asarray(g.nbrs)
    wgts = np.asarray(g.wgts).astype(np.float64)
    eu = np.asarray(g.edge_u)
    r = np.concatenate(
        [np.asarray(jax.random.normal(jax.random.fold_in(key, i),
                                      (g.n, 32), dtype=np.float32))
         for i in range(-(-samples // 32))], axis=1)[:, :samples]
    ints = []
    for v in range(g.n):
        acc = r[v].astype(np.float64)               # w(v, v) = 1
        for i in range(off[v], off[v + 1]):
            acc = acc + wgts[i] * r[nbrs[i]]
        ints.append(int("".join("1" if s >= 0 else "0" for s in acc), 2))
    cdeg = np.diff(off) + 1
    k = np.float32(samples)
    out = np.zeros(g.m2, np.float32)
    for i in range(g.m2):
        h = (ints[eu[i]] ^ ints[nbrs[i]]).bit_count()
        out[i] = max(np.float32(0.0),
                     np.cos(np.float32(np.pi) * np.float32(h) / k))
    if degree_heuristic:
        exact = (cdeg[eu] <= samples) | (cdeg[nbrs] <= samples)
        if exact.any():
            out[exact] = similarities_ref(g, "cosine", float32=True)[exact]
    return out


def index_orders_ref(g: CSRGraph, sims: np.ndarray) -> dict:
    """NO and CO of ``g`` under ``sims`` (per half-edge, graph order), by
    the definitions: each closed row sorted by σ descending with the
    vertex itself (σ = 1) first among equals, then neighbour id
    ascending; CO holds every slot at position μ ≥ 2 of its row, sorted
    by (μ ascending, θ descending, vertex ascending), ``co_offsets[μ]``
    the start of μ's segment. → dict named as the index fields."""
    off = np.asarray(g.offsets)
    nbrs = np.asarray(g.nbrs)
    sims = np.asarray(sims, np.float32)
    no_nbrs, no_sims, no_self, co = [], [], [], []
    for v in range(g.n):
        row = [(-np.float32(1.0), 0, v)] + [
            (-sims[i], 1, int(nbrs[i])) for i in range(off[v], off[v + 1])]
        row.sort()
        for pos, (neg_s, notself, x) in enumerate(row):
            no_nbrs.append(x)
            no_sims.append(-neg_s)
            no_self.append(notself == 0)
            if pos + 1 >= 2:
                co.append((pos + 1, neg_s, v))
    co.sort()
    cdeg = np.diff(off) + 1
    counts = np.bincount([mu for mu, _, _ in co],
                         minlength=int(cdeg.max()) + 1 if g.n else 1)
    return dict(
        offsets_c=off + np.arange(g.n + 1),
        no_nbrs=np.asarray(no_nbrs, np.int64),
        no_sims=np.asarray(no_sims, np.float32),
        no_self=np.asarray(no_self, bool),
        cdeg=cdeg,
        co_vertex=np.asarray([v for _, _, v in co], np.int64),
        co_theta=np.asarray([-s for _, s, _ in co], np.float32),
        co_offsets=np.concatenate([[0], np.cumsum(counts)]),
    )


def scan_ref(
    g: CSRGraph,
    mu: int,
    eps: float,
    measure: str = "cosine",
    sims: np.ndarray | None = None,
):
    """Full SCAN clustering. Returns dict with labels / is_core / is_hub /
    is_outlier (labels = min core id of the cluster, -1 unclustered)."""
    off = np.asarray(g.offsets)
    nbrs = np.asarray(g.nbrs)
    eu = np.asarray(g.edge_u)
    n = g.n
    if sims is None:
        sims = similarities_ref(g, measure)

    eps = np.float32(eps)  # match the parallel path's f32 threshold exactly
    smap: Dict[Tuple[int, int], float] = {}
    for i in range(g.m2):
        smap[(int(eu[i]), int(nbrs[i]))] = np.float32(sims[i])

    # ε-neighborhood sizes over closed neighborhoods (self always counts)
    is_core = np.zeros(n, dtype=bool)
    for v in range(n):
        cnt = 1  # σ(v,v) = 1 ≥ ε
        for x in _neigh(g, off, nbrs, v):
            if smap[(v, int(x))] >= eps:
                cnt += 1
        is_core[v] = cnt >= mu

    # BFS over cores through ε-similar core-core edges
    labels = np.full(n, -1, dtype=np.int64)
    comp = {}
    for s in range(n):
        if not is_core[s] or s in comp:
            continue
        group = [s]
        comp[s] = s
        q = deque([s])
        while q:
            u = q.popleft()
            for x in _neigh(g, off, nbrs, u):
                x = int(x)
                if is_core[x] and x not in comp and smap[(u, x)] >= eps:
                    comp[x] = s
                    group.append(x)
                    q.append(x)
        rep = min(group)
        for u in group:
            labels[u] = rep

    # border vertices: non-core, ε-similar to a core → most similar core,
    # ties to lower core id (deterministic §7.3.4 variant)
    for v in range(n):
        if is_core[v]:
            continue
        best = None
        for x in _neigh(g, off, nbrs, v):
            x = int(x)
            if is_core[x] and smap[(v, x)] >= eps:
                cand = (-smap[(v, x)], x)
                if best is None or cand < best:
                    best = cand
        if best is not None:
            labels[v] = labels[best[1]]

    # hubs / outliers among unclustered
    is_hub = np.zeros(n, dtype=bool)
    is_outlier = np.zeros(n, dtype=bool)
    for v in range(n):
        if labels[v] >= 0:
            continue
        neigh_clusters = {int(labels[int(x)]) for x in _neigh(g, off, nbrs, v)}
        neigh_clusters.discard(-1)
        if len(neigh_clusters) >= 2:
            is_hub[v] = True
        else:
            is_outlier[v] = True

    return dict(
        labels=labels,
        is_core=is_core,
        is_hub=is_hub,
        is_outlier=is_outlier,
        sims=sims,
    )
