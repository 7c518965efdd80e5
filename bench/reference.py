"""Plain SCAN reference: the same semantics as the system under test,
written independently of it (numpy, scipy and ``jax.numpy`` only; nothing
of the program is imported, and nothing the program made is read).

Semantics (the configuration files state them):

* graph: undirected, simple (self-loops and duplicate edges dropped),
  every weight 1; CSR rows ascending.
* σ(u, v): cosine over closed neighbourhoods in float32,
  ``|N̄(u) ∩ N̄(v)| / (sqrt(|N̄(u)|) · sqrt(|N̄(v)|))``.
* neighbour order NO: each closed row sorted by σ descending, the vertex
  itself (σ = 1) first among equals, then neighbour id ascending.
* core order CO: every slot at position μ ≥ 2 of its NO row, sorted by
  (μ ascending, θ descending, vertex ascending), with the start of each μ.
* a (μ, ε) query: v is a core iff μ ≥ 2 and at least μ members of N̄(v)
  have σ ≥ ε; clusters are the components of the cores under core–core
  edges with σ ≥ ε, labelled by their smallest vertex id; a non-core
  vertex joins the cluster of its most similar core neighbour with σ ≥ ε
  (ties to the lower core id), else it is labelled −1.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """Canonical edge set: ``lo < hi``, unique, sorted by (lo, hi); ``tri``
    holds |N(lo) ∩ N(hi)| per edge once :meth:`with_triangles` ran."""

    n: int
    lo: np.ndarray
    hi: np.ndarray
    tri: np.ndarray | None = None

    @staticmethod
    def from_pairs(n: int, pairs: np.ndarray) -> "Graph":
        pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
        pairs = pairs[pairs[:, 0] != pairs[:, 1]]
        key = np.unique(np.minimum(pairs[:, 0], pairs[:, 1]) * n
                        + np.maximum(pairs[:, 0], pairs[:, 1]))
        return Graph(n, key // n, key % n)

    @property
    def m(self) -> int:
        return len(self.lo)

    def keys(self) -> np.ndarray:
        return self.lo * self.n + self.hi

    def degrees(self) -> np.ndarray:
        return (np.bincount(self.lo, minlength=self.n)
                + np.bincount(self.hi, minlength=self.n))

    def relabel(self, perm: np.ndarray) -> "Graph":
        """The same graph with vertex ``v`` renamed ``perm[v]`` (triangle
        counts travel with their edges)."""
        a, b = perm[self.lo], perm[self.hi]
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        order = np.argsort(lo * self.n + hi, kind="stable")
        tri = None if self.tri is None else self.tri[order]
        return Graph(self.n, lo[order], hi[order], tri)

    def with_triangles(self, block: int = 1 << 24) -> "Graph":
        """Per-edge triangle counts: orient every edge from lower to higher
        (degree, id) rank, list each triangle once at its lowest vertex
        (a wedge of two out-edges closed by an edge), credit its three
        edges."""
        n, m = self.n, self.m
        deg = self.degrees()
        rank = np.empty(n, np.int64)
        rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
        ra, rb = rank[self.lo], rank[self.hi]
        src, dst = np.minimum(ra, rb), np.maximum(ra, rb)
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        okey = src * n + dst
        outdeg = np.bincount(src, minlength=n)
        start = np.concatenate([[0], np.cumsum(outdeg)])
        later = outdeg[src] - (np.arange(m) - start[src]) - 1
        cum = np.concatenate([[0], np.cumsum(later)])
        tri_o = np.zeros(m, np.int64)
        s = 0
        while s < m:
            e = int(np.searchsorted(cum, cum[s] + block, side="right")) - 1
            e = min(max(e, s + 1), m)
            r = later[s:e]
            i = np.repeat(np.arange(s, e), r)
            j = i + 1 + (np.arange(len(i)) - np.repeat(np.cumsum(r) - r, r))
            q = dst[i] * n + dst[j]
            p = np.minimum(np.searchsorted(okey, q), m - 1)
            hit = okey[p] == q
            for col in (i[hit], j[hit], p[hit]):
                tri_o += np.bincount(col, minlength=m)
            s = e
        tri = np.empty(m, np.int64)
        tri[order] = tri_o
        return dataclasses.replace(self, tri=tri)

    def csr(self):
        """→ (offsets int64[n+1], edge_u, nbrs, tri per half-edge), rows
        ascending, neighbours ascending within a row."""
        u = np.concatenate([self.lo, self.hi])
        v = np.concatenate([self.hi, self.lo])
        t = (np.concatenate([self.tri, self.tri]) if self.tri is not None
             else None)
        order = np.lexsort((v, u))
        offsets = np.concatenate(
            [[0], np.cumsum(np.bincount(u, minlength=self.n))])
        return (offsets, u[order], v[order],
                None if t is None else t[order])


def sigma(edge_u, nbrs, tri, offsets, dtype="float32"):
    """σ per half-edge in ``dtype``, on the default JAX device (the
    precision the configuration states; a lower one is the control), as
    one compiled expression so that the device's own sqrt and divide are
    used."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def cosine(a, b, c):
        a, b, c = (x.astype(dtype) for x in (a, b, c))
        return (c / (jnp.sqrt(a) * jnp.sqrt(b))).astype(jnp.float32)

    deg = np.diff(offsets)
    return np.asarray(cosine((deg[edge_u] + 1).astype(np.float32),
                             (deg[nbrs] + 1).astype(np.float32),
                             (tri + 2).astype(np.float32)))


def orders(offsets, edge_u, nbrs, sims):
    """NO and CO of a graph under ``sims`` → dict of arrays named as the
    index fields they stand for."""
    n = len(offsets) - 1
    rows = np.concatenate([np.arange(n), edge_u])
    nb = np.concatenate([np.arange(n), nbrs])
    s = np.concatenate([np.ones(n, np.float32), sims.astype(np.float32)])
    notself = np.concatenate([np.zeros(n, np.int8), np.ones(len(nbrs),
                                                             np.int8)])
    perm = np.lexsort((nb, notself, -s, rows))
    cdeg = np.diff(offsets) + 1
    offsets_c = offsets + np.arange(n + 1)
    no_rows = rows[perm]
    mu = np.arange(len(perm)) - offsets_c[no_rows] + 1
    co = mu >= 2
    cv, ct, cm = no_rows[co], s[perm][co], mu[co]
    cperm = np.lexsort((cv, -ct, cm))
    counts = np.bincount(cm, minlength=int(cdeg.max()) + 1 if n else 1)
    return {
        "offsets_c": offsets_c,
        "no_nbrs": nb[perm],
        "no_sims": s[perm],
        "no_self": notself[perm] == 0,
        "cdeg": cdeg,
        "co_vertex": cv[cperm],
        "co_theta": ct[cperm],
        "co_offsets": np.concatenate([[0], np.cumsum(counts)]),
    }


def cluster(n, edge_u, nbrs, sims, mu: int, eps: float):
    """One (μ, ε) query → (labels int64[n], is_core bool[n])."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    eps = np.float32(eps)
    ok = sims >= eps
    count = 1 + np.bincount(edge_u[ok], minlength=n)
    core = (mu >= 2) & (count >= mu)
    cc = ok & core[edge_u] & core[nbrs]
    adj = coo_matrix((np.ones(int(cc.sum()), np.int8),
                      (edge_u[cc], nbrs[cc])), shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    smallest = np.full(n, n, np.int64)
    np.minimum.at(smallest, comp[core], np.flatnonzero(core))
    labels = np.where(core, smallest[comp], -1)
    # borders: non-core row vertex v, core neighbour u, σ ≥ ε
    b = ok & ~core[edge_u] & core[nbrs]
    bv, bu, bs = edge_u[b], nbrs[b], sims[b]
    pick = np.lexsort((bu, -bs, bv))
    bv, bu = bv[pick], bu[pick]
    first = np.concatenate([[True], bv[1:] != bv[:-1]]) if len(bv) else \
        np.zeros(0, bool)
    labels[bv[first]] = labels[bu[first]]
    return labels, core
