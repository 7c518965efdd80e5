"""Plain reference of the sketched configuration: SimHash σ̂ under the
§6.3 degree heuristic, written independently of the program (numpy and
scipy; ``jax.random`` only to regenerate the projections R, which are the
configuration's data as weights would be, and the float32 σ of
``bench/reference.py``). Nothing of the program is imported.

Semantics (the configuration file's ``guarantees``):

* a vertex is a hub when its closed degree exceeds k; an edge is
  sketched when both endpoints are hubs, and keeps its exact float32 σ
  otherwise;
* bit j of v is ``Σ_{x∈N̄(v)} R[x, j] ≥ 0`` (every weight 1), with
  ``R[:, 32i:32i+32] = normal(fold_in(key, i), (n, 32))`` in float32;
  here the sums are taken in float64 over those float32 values;
* h(u, v) = popcount(sketch(u) XOR sketch(v)); σ̂ = max(0, cos(π·h/k)).

:func:`compare` reads the served σ̂ back to h through the float32 table
``max(0, cos(π·h/k))`` (nearest entry; every clipped σ̂ reads k/2, and
matches any h ≥ k/2) and counts, per build:

* ``exact_sigma_bad``: exact half-edges whose σ differs bit for bit;
* ``sketch_h_off``: sketched half-edges whose h differs from the float64
  sums' h, as a share;
* ``sketch_h_far``: sketched half-edges whose h lies outside what float32
  rounding of the sums allows. A hub's bit is *ambiguous* when its
  float64 sum lies within γ_{d−1}·Σ|x| of 0, the bound on the rounding
  error of d float32 terms added in any order (Higham, *Accuracy and
  Stability of Numerical Algorithms*, §4.2): a float32 sum may take
  either sign there and nowhere else. An edge's h then lies between the
  popcount of its unambiguous XOR bits and that count plus the positions
  where either endpoint's bit is ambiguous; with no such position h is
  exact;
* ``sketch_sigma_ulps``: the largest distance, in float32 ulps, of a
  sketched σ̂ from the table's entry for its h (the cos epilogue);
* ``theta_tail_share``: sketched half-edges with
  |h/k − arccos(σ)/π| > 0.1 against the exact σ (Hoeffding bounds its
  probability by 2·exp(−2·k·0.01));
* ``order_bad``: NO and CO slots against the orders of the served σ̂.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness, reference

THETA_GAP = 0.1
# readings of one build that are a share or a largest value: over several
# builds the worst of them counts; every other reading is a count and adds up
WORST = ("sketch_h_off", "sketch_sigma_ulps", "theta_tail_share")


def projections(n: int, samples: int, seed: int) -> np.ndarray:
    """R float32[n, samples]: word i from ``normal(fold_in(key, i))``."""
    import jax

    key = jax.random.PRNGKey(seed)
    words = -(-samples // 32)
    return np.concatenate(
        [np.asarray(jax.random.normal(jax.random.fold_in(key, i), (n, 32),
                                      dtype=np.float32))
         for i in range(words)], axis=1)[:, :samples]


def round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    """``x`` rounded to ``dtype``, a precision below float32, as float32."""
    import ml_dtypes
    return x.astype(getattr(ml_dtypes, dtype)).astype(np.float32)


def hub_sums(offsets, edge_u, nbrs, hubs, r):
    """float64 sums s[len(hubs), k] of the hubs' closed neighbourhoods
    over the float32 R, and the bound on how far a float32 sum of the
    same terms, in any order, can lie from each: γ_{d−1}·Σ|x| with d the
    closed degree and γ_j = j·u/(1 − j·u), u = 2⁻²⁴, widened by the
    same bound for the float64 sums themselves (u = 2⁻⁵³)."""
    from scipy.sparse import csr_matrix

    n = len(offsets) - 1
    a = csr_matrix((np.ones(len(nbrs)), (edge_u, nbrs)), shape=(n, n))[hubs]
    r64 = r.astype(np.float64)
    s = a @ r64 + r64[hubs]
    size = a @ np.abs(r64) + np.abs(r64[hubs])
    j = np.diff(offsets)[hubs].astype(np.float64)   # additions: d − 1
    gamma = sum(j * u / (1.0 - j * u) for u in (2.0 ** -24, 2.0 ** -53))
    return s, gamma[:, None] * size


def hub_sketches(offsets, edge_u, nbrs, hubs, r, dtype="float64"):
    """Packed sketches uint8[len(hubs), k/8] of the hubs' closed
    neighbourhoods and their ambiguous bits (:func:`hub_sums`), packed
    alike. ``float64``: one sparse product in float64. A lower precision:
    each partial sum rounded to it, neighbour by neighbour (the control),
    with the ambiguity of the float64 sums."""
    s, bound = hub_sums(offsets, edge_u, nbrs, hubs, r)
    amb = np.packbits(np.abs(s) <= bound, axis=1)
    if dtype != "float64":
        rr = round_to(r, dtype)
        deg = np.diff(offsets)[hubs]
        start = offsets[hubs]
        s = rr[hubs].copy()
        for j in range(int(deg.max()) if len(hubs) else 0):
            live = np.flatnonzero(deg > j)
            s[live] = round_to(s[live] + rr[nbrs[start[live] + j]], dtype)
    return np.packbits(s >= 0, axis=1), amb


def popcount(x: np.ndarray) -> np.ndarray:
    """Set bits per row of packed uint8 rows."""
    return np.bitwise_count(x).sum(axis=1, dtype=np.int64)


def h_table(samples: int) -> np.ndarray:
    """float32 max(0, cos(π·h/k)) for h = 0 .. k/2."""
    h = np.arange(samples // 2 + 1, dtype=np.float32)
    theta = np.float32(np.pi) * h / np.float32(samples)
    return np.maximum(np.float32(0.0), np.cos(theta))


def ulps(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """|a − b| in float32 ulps: the distance of the two values on the
    ordered line of float32 bit patterns."""
    def line(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return np.abs(line(a) - line(b))


def h_of(sims: np.ndarray, samples: int) -> np.ndarray:
    """h read back from σ̂: the nearest entry of :func:`h_table`."""
    table = h_table(samples).astype(np.float64)
    mid = (table[:-1] + table[1:]) / 2            # descending
    return np.searchsorted(-mid, -np.asarray(sims, np.float64), "left")


@dataclasses.dataclass
class RefApprox:
    """The reference's graph, exact σ, sketched mask and h for one
    build."""

    offsets: np.ndarray
    edge_u: np.ndarray
    nbrs: np.ndarray
    sims: np.ndarray        # exact float32 σ per half-edge
    sketched: np.ndarray    # bool per half-edge
    h: np.ndarray           # h per sketched half-edge
    h_lo: np.ndarray        # least h float32 rounding allows, per sketched
    h_hi: np.ndarray        # greatest
    xor: np.ndarray         # packed sketch XOR per sketched half-edge
    samples: int

    @staticmethod
    def build(graph: reference.Graph, sketch: dict,
              dtype: str = "float64") -> "RefApprox":
        samples = int(sketch["samples"])
        offsets, eu, nb, tri = graph.csr()
        sims = np.clip(reference.sigma(eu, nb, tri, offsets), 0.0, 1.0)
        cdeg = np.diff(offsets) + 1
        high = cdeg > samples
        sketched = (high[eu] & high[nb] if sketch["degree_heuristic"]
                    else np.ones(len(eu), bool))
        hubs = np.flatnonzero(high if sketch["degree_heuristic"]
                              else np.ones(len(cdeg), bool))
        x = np.zeros((0, samples // 8), np.uint8)
        h = h_lo = h_hi = np.zeros(0, np.int64)
        if sketched.any():
            r = projections(len(cdeg), samples, int(sketch["seed"]))
            bits, amb = hub_sketches(offsets, eu, nb, hubs, r, dtype)
            row = np.full(len(cdeg), -1, np.int64)
            row[hubs] = np.arange(len(hubs))
            a, b = row[eu[sketched]], row[nb[sketched]]
            x = bits[a] ^ bits[b]
            either = amb[a] | amb[b]
            h = popcount(x)
            h_lo = popcount(x & ~either)
            h_hi = h_lo + popcount(either)
        return RefApprox(offsets, eu, nb, sims, sketched, h, h_lo, h_hi, x,
                         samples)

    def served_sims(self) -> np.ndarray:
        """σ̂ as the configuration defines it from this reference's h."""
        out = self.sims.copy()
        out[self.sketched] = h_table(self.samples)[
            np.minimum(self.h, self.samples // 2)]
        return out


def compare(got_index: dict, got_graph: dict, ref: RefApprox) -> dict:
    """The checks of one served approximate index (module docstring)."""
    k = ref.samples
    graph_bad = (harness.mismatches(got_graph["offsets"], ref.offsets)
                 + harness.mismatches(got_graph["nbrs"], ref.nbrs))
    sims = np.asarray(got_index["edge_sims"])
    m2 = len(ref.nbrs)
    n_sk = int(ref.sketched.sum())
    if graph_bad or sims.shape != (m2,):
        return {"graph_bad": max(graph_bad, 1), "exact_sigma_bad": m2,
                "sketch_h_off": 1.0, "sketch_h_far": n_sk,
                "sketch_sigma_ulps": 2 ** 31, "theta_tail_share": 1.0,
                "order_bad": m2}
    exact = ~ref.sketched
    got = sims[ref.sketched]
    got_h = h_of(got, k)
    half = k // 2
    lo, hi = np.minimum(ref.h_lo, half), np.minimum(ref.h_hi, half)
    theta = np.arccos(np.clip(ref.sims[ref.sketched].astype(np.float64),
                              -1.0, 1.0)) / np.pi
    tail = np.abs(got_h / k - theta) > THETA_GAP
    orders = reference.orders(ref.offsets, ref.edge_u, ref.nbrs, sims)
    return {
        "graph_bad": 0,
        "exact_sigma_bad": harness.mismatches(sims[exact], ref.sims[exact]),
        "sketch_h_off": float((got_h != np.minimum(ref.h, half)).sum()
                              / max(n_sk, 1)),
        "sketch_h_far": int(((got_h < lo) | (got_h > hi)).sum()),
        "sketch_sigma_ulps": int(ulps(got, h_table(k)[got_h]).max()
                                 if n_sk else 0),
        "theta_tail_share": float(tail.sum() / max(n_sk, 1)),
        "order_bad": sum(harness.mismatches(got_index[f], orders[f])
                         for f in harness.INDEX_FIELDS),
    }


def agreement(ref: RefApprox, mu: int, eps: float) -> dict:
    """How far the reference's approximate clustering at (μ, ε) is from
    its exact one: shares of vertices with another label or core bit."""
    n = len(ref.offsets) - 1
    la, ca = reference.cluster(n, ref.edge_u, ref.nbrs, ref.served_sims(),
                               mu, eps)
    le, ce = reference.cluster(n, ref.edge_u, ref.nbrs, ref.sims, mu, eps)
    return {"mu": mu, "eps": eps, "label_diff": float((la != le).mean()),
            "core_diff": float((ca != ce).mean())}
