"""Chung–Lu power-law edge list (the benchmark's own copy).

Vertex attachment weights follow w_i ∝ (i+1)^(-1/(α-1)), the expected
degree sequence of a power-law graph with exponent α; endpoints are drawn
∝ w. ``max_degree`` caps every expected degree (the excess weight spreads
over the uncapped vertices) and ``hub_degree`` wires vertex 0 to that many
distinct random vertices. ``n·avg_degree`` endpoint pairs are kept before
self-loops and duplicates drop, so the mean degree comes out near
``2·avg_degree``.

Draws the same numbers in the same order as
``repro.core.graph.power_law_graph``, so a seed gives that function's
graph; it is copied here so that a change to the program cannot move the
benchmark's input.
"""
from __future__ import annotations

import numpy as np


def edges(*, n: int, alpha: float, avg_degree: float, hub_degree: int,
          max_degree: int, seed: int) -> tuple[int, np.ndarray]:
    """→ (n, int64[E, 2] raw endpoint pairs, self-loops and duplicates
    included)."""
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
    return n, e.astype(np.int64)
