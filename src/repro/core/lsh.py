"""LSH-approximate similarities (paper §5, §6.3).

* SimHash (cosine, weighted or unweighted): sketch(v) = sign(N̄_w(v) · R),
  R ∈ ℝ^{n×k} i.i.d. N(0,1). The kn dot products are one (sparse) matmul —
  on TPU this is the Pallas ``simhash`` kernel's blocked MXU matmul; here the
  sparse gather/segment-sum form is used. Bits are packed into uint32 lanes;
  per-edge comparison is XOR + popcount (``lax.population_count``), the
  Pallas ``hamming`` kernel's job on TPU.
  Estimate: θ̂ = π·(#differing bits)/k, σ̂ = cos(θ̂)  — Theorem 5.2 applies.

* MinHash (Jaccard, unweighted): k independent universal hashes
  h_i(x) = (aᵢ·x + bᵢ) mod p; sketch(v)ᵢ = min_{x∈N̄(v)} hᵢ(x).
  Estimate: fraction of matching coordinates — Theorem 5.3 applies.

* k-partition MinHash / one-permutation hashing (fast path, §6.3): a single
  permutation π, k buckets, per-bucket min of π over N̄(v); empty buckets
  densified by circular borrowing (rotation). No tail bound (paper says the
  same), lower variance in practice.

Degree heuristic (§6.3): approximate only edges whose *both* endpoints have
closed degree above a threshold (k for cosine, 3k/2 for Jaccard); all other
edges get exact similarities, computed only on that compacted subset via
the degree-bucketed engine (:class:`repro.core.similarity.SimilarityPlan`),
once per undirected edge.
The heuristic and the bucketed layout compose naturally: every exact edge
has a low-degree endpoint, so its probe routes to a small degree class —
the exact pass never touches a hub-width kernel.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import CSRGraph
from repro.core import similarity as sim_mod
from repro.kernels.hamming import hamming_sigma
from repro.kernels.ref import hamming_distance_ref
from repro.obs.trace import maybe_span


# --------------------------------------------------------------------------
# SimHash
# --------------------------------------------------------------------------
# element budget of one sketch chunk's (half-edges × samples) projection
# gather: 2²⁸ float32 = 1 GiB of transient device memory
SKETCH_CHUNK_ELEMS = 1 << 28


def simhash_sketches(g: CSRGraph, samples: int, key: jax.Array,
                     *, chunk: Optional[int] = None) -> jax.Array:
    """Packed sketches uint32[n, ceil(k/32)] of closed weighted neighborhoods.

    ``chunk`` samples are projected per pass, which bounds the (m2, chunk)
    gathered projections; it is a *memory* knob only, by default the
    largest multiple of 32 (at most 512) within ``SKETCH_CHUNK_ELEMS``.
    Each 32-sample word derives its projections from
    ``fold_in(key, word_index)``, so the sketch bits — and therefore σ̂ and
    every downstream index fingerprint — are invariant to the chunking.
    (The old per-chunk ``fold_in(key, w0)`` keyed the randomness on the
    chunk boundary itself: changing the chunk width silently changed every
    sketch.)
    """
    if chunk is None:
        chunk = min(max(SKETCH_CHUNK_ELEMS // max(g.m2, 1) // 32, 1), 16) * 32
    if chunk % 32 != 0 or chunk <= 0:
        raise ValueError(f"chunk must be a positive multiple of 32: {chunk}")
    k_pad = (samples + 31) // 32 * 32
    words = []
    for w0 in range(0, k_pad, chunk):  # chunk the sample axis to bound memory
        kw = min(chunk, k_pad - w0)
        words.append(_simhash_chunk(g.edge_u, g.nbrs, g.wgts, key,
                                    w0 // 32, g.n, kw, samples - w0))
    return jnp.concatenate(words, axis=1)


@functools.partial(jax.jit, static_argnames=("word0", "n", "kw", "valid"))
def _simhash_chunk(edge_u, nbrs, wgts, key, word0, n, kw, valid):
    # one fold_in per 32-sample word: bit w's projection column depends only
    # on (key, w // 32), never on which chunk it was generated in
    word_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, word0 + jnp.arange(kw // 32))
    r = jax.vmap(lambda k: jax.random.normal(k, (n, 32), dtype=jnp.float32),
                 out_axes=1)(word_keys)                  # [n, kw/32, 32]
    r = r.reshape(n, kw)
    if valid < kw:  # zero out padding samples → identical bits on both sides
        r = r * (jnp.arange(kw) < valid)
    s = r + jax.ops.segment_sum(wgts[:, None] * r[nbrs], edge_u, num_segments=n)
    bits = (s >= 0.0) & (jnp.arange(kw) < max(valid, 0))
    bits = bits.reshape(n, kw // 32, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None, :]
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def simhash_edge_similarity(
    sketches: jax.Array, eu: jax.Array, ev: jax.Array, samples: int
) -> jax.Array:
    """cos(π·hamming/k) per edge from packed sketches: the ``hamming`` op's
    ref lane, ending in the :func:`~repro.kernels.hamming.hamming_sigma`
    epilogue that every lane shares."""
    return hamming_sigma(_hamming_distance(sketches, eu, ev), samples)


@jax.jit
def _hamming_distance(sketches, eu, ev):
    return hamming_distance_ref(sketches[eu], sketches[ev])


# --------------------------------------------------------------------------
# standard MinHash — k independent uniformly random permutations (§2.1.2)
# --------------------------------------------------------------------------
def minhash_sketches(g: CSRGraph, samples: int, key: jax.Array,
                     *, chunk: int = 64) -> jax.Array:
    """Sketches int32[n, k]: sketch(v)ᵢ = min_{x∈N̄(v)} πᵢ(x).

    Permutation i is keyed by ``fold_in(key, i)`` — chunking (the memory
    knob) never changes the sketch, mirroring ``simhash_sketches``.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive: {chunk}")
    out = []
    for s0 in range(0, samples, chunk):  # chunk the sample axis
        kc = min(chunk, samples - s0)
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            key, s0 + jnp.arange(kc))
        out.append(_minhash_chunk(g.edge_u, g.nbrs, keys, g.n))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("n",))
def _minhash_chunk(edge_u, nbrs, keys, n):
    perms = jax.vmap(lambda k: jax.random.permutation(k, n))(keys)  # [kc, n]
    perms = perms.astype(jnp.int32).T                               # [n, kc]
    big = jnp.int32(np.iinfo(np.int32).max)
    mins = (
        jnp.full((n, perms.shape[1]), big, dtype=jnp.int32)
        .at[edge_u]
        .min(perms[nbrs])
    )
    return jnp.minimum(mins, perms)


@jax.jit
def minhash_edge_similarity(sketches, eu, ev):
    return jnp.mean(sketches[eu] == sketches[ev], axis=-1).astype(jnp.float32)


# --------------------------------------------------------------------------
# k-partition MinHash (one-permutation hashing + rotation densification)
# --------------------------------------------------------------------------
def kpartition_sketches(g: CSRGraph, samples: int, key: jax.Array) -> jax.Array:
    perm = jax.random.permutation(key, g.n).astype(jnp.int32)
    return _kpartition_build(g.edge_u, g.nbrs, perm, g.n, samples)


@functools.partial(jax.jit, static_argnames=("n", "k"))
def _kpartition_build(edge_u, nbrs, perm, n, k):
    big = jnp.int32(np.iinfo(np.int32).max)

    def bucket_val(x):
        px = perm[x]
        # (px * k) // n in int32 — requires n·k < 2^31 (documented constraint)
        return (px * jnp.int32(k)) // jnp.int32(n), px

    bk_n, val_n = bucket_val(nbrs)
    bk_s, val_s = bucket_val(jnp.arange(n, dtype=jnp.int32))
    flat = jnp.full((n * k,), big)
    flat = flat.at[edge_u * k + bk_n].min(val_n)
    flat = flat.at[jnp.arange(n, dtype=jnp.int32) * k + bk_s].min(val_s)
    sk = flat.reshape(n, k)

    # rotation densification: an empty bin borrows from a non-empty bin to
    # its right (circular), offset by borrow distance so bins densified from
    # different distances never spuriously match. Doubling ⇒ log2(k) rounds.
    val = sk
    dist = jnp.where(sk == big, big, 0)
    t = 0
    while (1 << t) < k:
        s = 1 << t
        cand_val = jnp.roll(val, -s, axis=1)
        cand_dist = jnp.roll(dist, -s, axis=1)
        take = (val == big) & (cand_val != big)
        val = jnp.where(take, cand_val, val)
        dist = jnp.where(take, cand_dist + s, dist)
        t += 1
    # encode (value, borrow distance) as one int32; requires (n+1)·k < 2^31
    return val + jnp.int32(n + 1) * dist


@jax.jit
def kpartition_edge_similarity(sketches, eu, ev):
    return jnp.mean(sketches[eu] == sketches[ev], axis=-1).astype(jnp.float32)


# --------------------------------------------------------------------------
# combined approximate-σ entry point with the §6.3 degree heuristic
# --------------------------------------------------------------------------
def approximate_similarities(
    g: CSRGraph,
    *,
    measure: str = "cosine",
    method: str = "simhash",
    samples: int = 64,
    key: Optional[jax.Array] = None,
    degree_heuristic: bool = True,
    policy=None,
    tracer=None,
) -> jax.Array:
    """σ̂ per half-edge. Sketch *construction* is always the chunk-invariant
    sparse jnp path (its bits define the approximate fingerprint); the
    sketch *comparison* resolves its lane through the execution policy —
    the ``hamming`` op's Pallas lanes consume the exact same sketches and
    reproduce the ``ref`` comparison bit-for-bit on every backend (each
    lane yields the integer-exact XOR/popcount distance, and all of them
    share one jitted cos epilogue), so lane choice never moves a
    fingerprint.

    With a ``tracer`` (:class:`repro.obs.Tracer`) the pass is four spans:
    ``lsh.sketch`` (every sketch chunk, to its device result),
    ``lsh.compare`` (the endpoint gathers and the comparison),
    ``lsh.exact_subset`` (the heuristic's mask, the plan and the exact
    pass, which computes σ on its ``edges`` upper half-edges and gives
    each twin the same bits, tracing itself as in
    :meth:`~repro.core.similarity.SimilarityPlan.half_edge_sims`) and
    ``lsh.merge`` (exact σ written into σ̂, the clip). Each span ends by
    waiting for its device results, timed as an ``lsh.device_wait``
    event; the half-edges given σ̂ and exact σ count under
    ``lsh.sketched_edges`` and ``lsh.exact_edges``. Without one nothing
    is timed and nothing waits."""
    from repro.backend.policy import default_policy

    pol = policy if policy is not None else default_policy()
    if key is None:
        key = jax.random.PRNGKey(0)
    if method == "simhash":
        if measure != "cosine":
            raise ValueError("simhash approximates cosine similarity")
        from repro.kernels import ops

        def sketch():
            return simhash_sketches(g, samples, key)

        def compare(sk):
            return ops.simhash_edge_similarity_kernel(
                sk, g.edge_u, g.nbrs, samples, policy=pol,
                lane=pol.lane("hamming"))
        thr = samples
    elif method in ("minhash", "kpartition"):
        if measure != "jaccard":
            raise ValueError("minhash approximates jaccard similarity")
        sketch_fn, compare_fn = (
            (minhash_sketches, minhash_edge_similarity) if method == "minhash"
            else (kpartition_sketches, kpartition_edge_similarity))

        def sketch():
            return sketch_fn(g, samples, key)

        def compare(sk):
            return compare_fn(sk, g.edge_u, g.nbrs)
        thr = (3 * samples) // 2
    else:
        raise ValueError(f"unknown LSH method {method!r}")

    with maybe_span(tracer, "lsh.sketch", method=method, samples=samples):
        sk = _wait(tracer, sketch())
    with maybe_span(tracer, "lsh.compare", edges=g.m2):
        approx = _wait(tracer, compare(sk))

    # §6.3: exact σ for edges where either endpoint is low-degree; the exact
    # pass runs only on the compacted subset (real work saving, not a mask)
    # through the bucketed plan — each exact edge probes its low-degree side,
    # so the subset routes to the small degree-class kernels only.
    idx = np.zeros(0, np.int64)
    exact_subset = None
    if degree_heuristic:
        with maybe_span(tracer, "lsh.exact_subset") as span:
            cdeg = np.asarray(g.closed_degrees())
            eu_h, ev_h = np.asarray(g.edge_u), np.asarray(g.nbrs)
            high = cdeg > thr
            # the mask is symmetric, so the subset is closed under twins:
            # σ is computed on its upper half only
            idx = np.nonzero(~(high[eu_h] & high[ev_h]))[0]
            if span is not None:
                span.set(edges=len(idx) // 2)
            if len(idx):
                exact_subset = sim_mod.plan_for(
                    g, tracer=tracer).half_edge_sims(
                        g, idx, measure=measure, policy=pol, tracer=tracer)
                _wait(tracer, exact_subset)
    if tracer is not None:
        tracer.inc("lsh.sketched_edges", g.m2 - len(idx))
        tracer.inc("lsh.exact_edges", len(idx))
    with maybe_span(tracer, "lsh.merge", edges=len(idx)):
        if exact_subset is None:            # pure-LSH path: no plan needed
            out = jnp.clip(approx, 0.0, 1.0)
        else:
            merged = np.asarray(approx, dtype=np.float32).copy()
            merged[idx] = np.asarray(exact_subset)
            out = jnp.clip(jnp.asarray(merged), 0.0, 1.0)
        return _wait(tracer, out)


def _wait(tracer, x):
    """``x`` once the device has it, the wait timed as an
    ``lsh.device_wait`` event; without a tracer ``x`` as it is."""
    if tracer is not None:
        t0 = time.perf_counter()
        jax.block_until_ready(x)
        tracer.event("lsh.device_wait", time.perf_counter() - t0)
    return x
