"""SCAN index construction (paper §4.1, Algorithms 1–2).

The index is the GS*-Index pair (neighbor order NO, core order CO), stored
as flat segmented arrays (all O(m)):

Neighbor order — the *closed* adjacency (each row = v plus its neighbors,
σ(v,v)=1) sorted within each row by descending σ. Built with **one global
sort** over all m2+n slots keyed by (row, -σ, ¬self, nbr) — exactly the
paper's "prepend v to every entry and sort everything once" integer-sort
trick (§4.1.2), mapped onto XLA's parallel sort.

Core order — for every (v, μ) with 2 ≤ μ ≤ |N̄(v)| the core threshold
θ(v, μ) is *already* the μ-th entry of NO[v], so CO is nothing more than a
re-sort of the NO slots by (μ, -θ, v): one more global sort, Σ(|N̄(v)|−1) =
2m entries, O(m) space — the same bound as GS*-Index.

Construction is host-orchestrated (graph building, padding, chunk loops)
around jit-compiled kernels; every array op is a bulk-parallel primitive
(sort / gather / scatter / segment ops) with O(log) span.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import CSRGraph
from repro.core import similarity as sim_mod
from repro.core import lsh as lsh_mod
from repro.obs.trace import maybe_span


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScanIndex:
    """GS*-Index analogue. All arrays live on device; n/m2c/max_cdeg static."""

    # --- closed CSR (rows include the self slot) ---
    offsets_c: jax.Array    # int32[n+1]  row starts (offsets[v] + v)
    # --- neighbor order (σ-descending within each row) ---
    no_nbrs: jax.Array      # int32[m2c]
    no_sims: jax.Array      # float32[m2c]
    no_self: jax.Array      # bool[m2c]   marks the self slot
    # --- core order (μ-major, θ-descending segments) ---
    co_offsets: jax.Array   # int32[max_cdeg+2]  segment start per μ (CO[μ])
    co_vertex: jax.Array    # int32[m2]
    co_theta: jax.Array     # float32[m2]
    # --- misc ---
    cdeg: jax.Array         # int32[n] closed degrees
    edge_sims: jax.Array    # float32[m2] σ per original half-edge (graph order)
    n: int = dataclasses.field(metadata=dict(static=True))
    m2c: int = dataclasses.field(metadata=dict(static=True))
    max_cdeg: int = dataclasses.field(metadata=dict(static=True))

    def core_threshold(self, mu: jax.Array) -> jax.Array:
        """θ(v, μ) for all v, float32[n]; -inf where |N̄(v)| < μ."""
        slot = self.offsets_c[:-1] + (mu - 1)
        valid = self.cdeg >= mu
        theta = self.no_sims[jnp.clip(slot, 0, self.m2c - 1)]
        return jnp.where(valid, theta, -jnp.inf)


def _desc_key(sims: jax.Array) -> jax.Array:
    """int32 sort key ascending in −σ: σ ∈ [0, 1] is a non-negative float,
    so its bit pattern orders like its value (−0.0 folds onto +0.0)."""
    return -jnp.maximum(jax.lax.bitcast_convert_type(sims, jnp.int32), 0)


@jax.jit
def _build_orders(offsets, edge_u, nbrs, sims, n_arr):
    """Global sorts for NO and CO. n_arr = jnp.arange(n).

    ``edge_u``/``nbrs``/``sims`` may carry padding slots past the real
    half-edges, with source row ``n``: they sort after every real slot of
    both orders, so the caller slices them off and a padded slot count
    (:func:`_slot_capacity`) lets nearby graph sizes share one compile.

    Both orders come from two-key stable sorts over int32 keys. The closed
    slots are the n self slots followed by the CSR's half-edges (ascending
    neighbors within each row), so a stable (row, −σ) sort yields NO's
    (row asc, σ desc, self first, nbr asc); a stable (μ, −θ) sort of the NO
    slots then yields CO's (μ asc, θ desc, v asc), since NO is row-major.
    Fewer, integer keys keep the TPU compile of the sorts short."""
    n = n_arr.shape[0]
    m2c = edge_u.shape[0] + n
    # ---- closed slot arrays: n self slots + the (padded) edge slots ----
    rows = jnp.concatenate([n_arr, edge_u])
    nbrs_c = jnp.concatenate([n_arr, nbrs])
    sims_c = jnp.concatenate([jnp.ones((n,), jnp.float32), sims])
    is_self = jnp.arange(m2c) < n
    # one global sort: row asc, σ desc; ties keep self-first, nbr asc
    with jax.named_scope("index.no_sort"):
        _, _, perm = jax.lax.sort(
            (rows, _desc_key(sims_c), jnp.arange(m2c, dtype=jnp.int32)),
            num_keys=2, is_stable=True)
    no_nbrs = nbrs_c[perm]
    no_sims = sims_c[perm]
    no_self = is_self[perm]
    rows_sorted = rows[perm]

    cdeg = jnp.diff(offsets) + 1
    offsets_c = offsets + jnp.arange(n + 1, dtype=offsets.dtype)

    # ---- core order: every slot with position μ ≥ 2 inside its row ----
    mu_of_slot = jnp.arange(m2c, dtype=jnp.int32) - offsets_c[rows_sorted] + 1
    is_co = (mu_of_slot >= 2) & (rows_sorted < n)
    # key sort: μ asc, θ desc; ties keep NO's row order (v asc); inactive
    # slots pushed to the end
    mu_key = jnp.where(is_co, mu_of_slot, jnp.int32(2**30))
    with jax.named_scope("index.co_sort"):
        _, _, perm2 = jax.lax.sort(
            (mu_key, _desc_key(no_sims), jnp.arange(m2c, dtype=jnp.int32)),
            num_keys=2, is_stable=True)
    co_vertex = rows_sorted[perm2]
    co_theta = no_sims[perm2]
    co_mu = mu_key[perm2]
    return (offsets_c, no_nbrs, no_sims, no_self, cdeg, co_vertex, co_theta, co_mu)


def _slot_capacity(m: int) -> int:
    """``m`` rounded up to one of 16 steps per octave (≤ 6.25 % padding),
    so a graph edited by a few edges keeps its compiled global sorts."""
    step = 1 << max(m.bit_length() - 5, 0)
    return -(-max(m, 1) // step) * step


def build_index(
    g: CSRGraph,
    measure: str = "cosine",
    *,
    approx: Optional[str] = None,     # None | "simhash" | "minhash" | "kpartition"
    samples: int = 64,
    key: Optional[jax.Array] = None,
    degree_heuristic: bool = True,
    sims: Optional[jax.Array] = None,  # precomputed σ override (testing)
    tracer=None,
) -> ScanIndex:
    """Construct the SCAN index (exact or LSH-approximate similarities).

    ``tracer`` (a :class:`repro.obs.Tracer`) wraps the build in an
    ``index.build`` span holding ``similarity.pass`` (when σ is computed
    here; the exact pass traces its plan, routing and groups, the LSH pass
    its ``lsh.*`` stages),
    ``index.orders`` (the global sorts, through the host read of their
    result) and ``index.co_offsets``. Without one nothing is timed."""
    with maybe_span(tracer, "index.build", n=g.n, m=g.m):
        if sims is None:
            with maybe_span(tracer, "similarity.pass",
                            approx=approx or "exact"):
                if approx is None:
                    sims = sim_mod.compute_similarities(g, measure,
                                                        tracer=tracer)
                else:
                    sims = lsh_mod.approximate_similarities(
                        g,
                        measure=measure,
                        method=approx,
                        samples=samples,
                        key=(key if key is not None
                             else jax.random.PRNGKey(0)),
                        degree_heuristic=degree_heuristic,
                        tracer=tracer,
                    )
        sims = jnp.clip(sims.astype(jnp.float32), 0.0, 1.0)

        with maybe_span(tracer, "index.orders"):
            n_arr = jnp.arange(g.n, dtype=jnp.int32)
            pad = _slot_capacity(g.m2) - g.m2
            (offsets_c, no_nbrs, no_sims, no_self, cdeg, co_vertex, co_theta,
             co_mu) = _build_orders(
                g.offsets,
                jnp.pad(g.edge_u, (0, pad), constant_values=g.n),
                jnp.pad(g.nbrs, (0, pad), constant_values=g.n),
                jnp.pad(sims, (0, pad)), n_arr)
            m2c = g.m2 + g.n
            no_nbrs, no_sims, no_self = (no_nbrs[:m2c], no_sims[:m2c],
                                         no_self[:m2c])
            co_vertex, co_theta, co_mu = (
                co_vertex[:g.m2], co_theta[:g.m2], co_mu[:g.m2])
            # the host read waits for the sorts, so the span covers them
            max_cdeg = int(np.asarray(cdeg).max()) if g.n else 1
        with maybe_span(tracer, "index.co_offsets"):
            # segment starts per μ;
            # CO[μ] = co_vertex[co_offsets[μ] : co_offsets[μ+1]]
            counts = np.bincount(np.asarray(co_mu), minlength=max_cdeg + 1)
            co_offsets = jnp.concatenate(
                [
                    jnp.zeros((1,), jnp.int32),
                    jnp.asarray(np.cumsum(counts), dtype=jnp.int32),
                ]
            )
        return ScanIndex(
            offsets_c=offsets_c,
            no_nbrs=no_nbrs,
            no_sims=no_sims,
            no_self=no_self,
            co_offsets=co_offsets,
            co_vertex=co_vertex,
            co_theta=co_theta,
            cdeg=cdeg,
            edge_sims=sims,
            n=g.n,
            m2c=m2c,
            max_cdeg=max_cdeg,
        )


def get_cores(index: ScanIndex, mu: int, eps: float) -> jax.Array:
    """bool[n] core mask (paper Algorithm 3).

    v is a core iff θ(v, μ) ≥ ε, and θ(v, μ) is the μ-th slot of NO[v]:
    one gather per vertex (O(n)). This is the set the θ ≥ ε prefix of
    CO[μ] holds, read point-wise instead of scattered from the CO slots (a
    scatter over m2 slots per query, which a TPU serializes).
    ``cdeg ≥ μ`` implies μ ≤ max_cdeg."""
    mu = jnp.asarray(mu, jnp.int32)
    eps = jnp.asarray(eps, jnp.float32)
    theta = index.core_threshold(mu)
    return (mu >= 2) & (index.cdeg >= mu) & (theta >= eps)
