"""Graph 500 Kronecker (R-MAT) edge list, after the specification's
reference generator (graph500.org, "kronecker_generator").

``edgefactor · 2^scale`` edges; each picks one quadrant per level with
initiator probabilities A, B, C (D = 1 − A − B − C), the vertex labels
are then permuted and the edge order shuffled. Self-loops and duplicate
edges are left in, as the specification leaves them; the graph built from
the list drops them.
"""
from __future__ import annotations

import numpy as np


def edges(*, scale: int, edgefactor: int, a: float, b: float, c: float,
          seed: int) -> tuple[int, np.ndarray]:
    """→ (n = 2^scale, int64[E, 2] raw endpoint pairs)."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for level in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << level
        ij[1] += jj.astype(np.int64) << level
    labels = rng.permutation(n)
    ij = labels[ij]
    ij = ij[:, rng.permutation(m)]
    return n, np.ascontiguousarray(ij.T)
