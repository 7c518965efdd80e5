"""Public wrappers around the Pallas kernels, dispatched per call.

Each wrapper pads inputs to block multiples, resolves its lane through the
:class:`repro.backend.ExecutionPolicy` (``ref`` pure-jnp oracle /
``pallas-interpret`` / ``pallas-compiled``), dispatches, and slices the
result back. Nothing here captures the backend at import time: platform
detection and the ``REPRO_LANE`` override are read on every call, so
``JAX_PLATFORMS`` set after import is honored and importing this module
never initializes the jax backend.

Block shapes default to the policy's :class:`AutotuneProfile`; explicit
``block=``/``be=``/``bt=`` arguments still win. All lanes of one op are
bit-identical on integer-valued inputs (unweighted graphs) and agree to
ULP on weighted ones — the lane-matrix oracle test in
``tests/test_backend.py`` is the gate.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.backend.padding import pad_to
from repro.backend.policy import (
    LANE_REF, ExecutionPolicy, default_policy,
)
from repro.core.graph import CSRGraph, to_dense
from repro.kernels import ref as kref
from repro.kernels.triangle_count import masked_gram
from repro.kernels.bucket_probe import bucket_probe
from repro.kernels.simhash import simhash_pack
from repro.kernels.hamming import hamming_distance, hamming_sigma


def _resolve(policy: Optional[ExecutionPolicy], op: str,
             lane: Optional[str]) -> tuple[ExecutionPolicy, str]:
    pol = policy if policy is not None else default_policy()
    if lane is None:
        lane = pol.kernel_lane(op)
    pol.note(op, lane)
    return pol, lane


def edge_similarities_gram(
    g: CSRGraph, measure: str = "cosine", block: Optional[int] = None,
    *, policy: Optional[ExecutionPolicy] = None, lane: Optional[str] = None,
) -> jax.Array:
    """Exact σ per half-edge via the masked-gram product (triangle_count op).

    Dense-adjacency path: the TPU-native analogue of Algorithm 1 for graphs
    whose adjacency fits in memory (padded n ≤ a few 10⁴ per shard; larger
    graphs use the CSR searchsorted path in core.similarity).
    """
    pol, lane = _resolve(policy, "triangle_count", lane)
    block = block or pol.profile.gram_block
    weighted = measure == "cosine"
    w = to_dense(g, closed=True, weighted=weighted)
    mask = (to_dense(g, closed=True, weighted=False) > 0).astype(jnp.float32)
    n0 = w.shape[0]
    w = pad_to(w, block, (0, 1))
    mask = pad_to(mask, block, (0, 1))
    if lane == LANE_REF:
        prod = kref.masked_gram_ref(w, mask)[:n0, :n0]
    else:
        prod = masked_gram(w, mask, bm=block, bn=block, bk=block,
                           interpret=pol.interpret(lane))[:n0, :n0]
    dots = prod[g.edge_u, g.nbrs]
    if measure == "cosine":
        norms = jnp.sqrt(prod[jnp.arange(n0), jnp.arange(n0)])
        return dots / (norms[g.edge_u] * norms[g.nbrs])
    cdeg = g.closed_degrees().astype(jnp.float32)
    union = cdeg[g.edge_u] + cdeg[g.nbrs] - dots
    return dots / union


def probe_operands(rows_p, w_p, rows_t, w_t, n: int, be: int, bt: int):
    """Sanitize + pad bucket-probe operands (trace-safe; shared with the
    similarity engine's Pallas lane).

    Sanitizes padding ids (probe → -1, target → -2 so pads never match),
    pads the edge axis to ``be``, the target width to ``bt`` (the hub-row
    tile the kernel streams) and the probe width to the kernel's probe
    chunk ``min(P, bt)``. Widens with the sentinel id ``n`` BEFORE
    sanitizing, so width padding becomes a pad id like every other (0
    would alias vertex id 0). Returns (ids_p, w_p, ids_t, w_t, bt).
    """
    t = rows_t.shape[1]
    bt = min(bt, max(t, 1))
    pc = min(rows_p.shape[1], bt)
    rows_p = pad_to(rows_p, pc, (1,), value=n)
    w_p = pad_to(w_p, pc, (1,))
    rows_t = pad_to(rows_t, bt, (1,), value=n)
    w_t = pad_to(w_t, bt, (1,))
    ids_p = jnp.where(rows_p < n, rows_p, -1).astype(jnp.int32)
    ids_t = jnp.where(rows_t < n, rows_t, -2).astype(jnp.int32)
    ids_p = pad_to(ids_p, be, (0,))
    w_p = pad_to(w_p, be, (0,))
    ids_t = pad_to(ids_t, be, (0,))
    w_t = pad_to(w_t, be, (0,))
    return ids_p, w_p, ids_t, w_t, bt


def bucket_probe_stats(
    rows_p: jax.Array,   # int32[e, P] sorted probe rows (pad id = n)
    w_p: jax.Array,      # float32[e, P]
    rows_t: jax.Array,   # int32[e, T] sorted target rows (pad id = n)
    w_t: jax.Array,      # float32[e, T]
    n: int,              # vertex count (ids ≥ n are padding)
    *,
    be: Optional[int] = None,
    bt: Optional[int] = None,
    policy: Optional[ExecutionPolicy] = None,
    lane: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    """(shared weighted dot, shared count) per edge via the degree-bucketed
    probe op (repro.kernels.bucket_probe; ref lane = the all-pairs
    equality oracle). The accelerator dispatch path for the heaviest
    degree classes; the jnp searchsorted engine in core.similarity is the
    host reference path."""
    pol, lane = _resolve(policy, "bucket_probe", lane)
    be = be or pol.profile.probe_be
    bt = bt or pol.profile.probe_bt
    e0 = rows_p.shape[0]
    ids_p, w_p, ids_t, w_t, bt = probe_operands(
        rows_p, w_p, rows_t, w_t, n, be, bt)
    if lane == LANE_REF:
        dot, cnt = kref.bucket_probe_ref(ids_p, w_p, ids_t, w_t)
    else:
        dot, cnt = bucket_probe(ids_p, w_p, ids_t, w_t, be=be, bt=bt,
                                interpret=pol.interpret(lane))
    return dot[:e0], cnt[:e0]


def simhash_sketches_kernel(
    g: CSRGraph, samples: int, key: jax.Array, block: Optional[int] = None,
    *, policy: Optional[ExecutionPolicy] = None, lane: Optional[str] = None,
) -> jax.Array:
    """Packed SimHash sketches uint32[n, ceil(k/32)] via the simhash op."""
    pol, lane = _resolve(policy, "simhash", lane)
    block = block or pol.profile.simhash_block
    w = to_dense(g, closed=True, weighted=True)
    n0 = w.shape[0]
    k_pad = max((samples + 127) // 128 * 128, 128)
    r = jax.random.normal(key, (n0, k_pad), dtype=jnp.float32)
    # zero padding samples so both endpoints agree on padded bits
    r = r * (jnp.arange(k_pad) < samples)
    w = pad_to(w, block, (0, 1))
    r = pad_to(r, block, (0,))
    if lane == LANE_REF:
        sk = kref.simhash_pack_ref(w, r)
    else:
        sk = simhash_pack(w, r, bm=block, bk=block,
                          interpret=pol.interpret(lane))
    return sk[:n0, : (samples + 31) // 32]


def simhash_edge_similarity_kernel(
    sketches: jax.Array, eu: jax.Array, ev: jax.Array, samples: int,
    block: Optional[int] = None,
    *, policy: Optional[ExecutionPolicy] = None, lane: Optional[str] = None,
) -> jax.Array:
    """σ̂ per edge from packed sketches via the hamming op. Every lane
    yields the integer Hamming distance; the shared :func:`hamming_sigma`
    epilogue turns it into σ̂, so lanes agree bit-for-bit on any chip."""
    pol, lane = _resolve(policy, "hamming", lane)
    block = block or pol.profile.hamming_block
    e0 = eu.shape[0]
    su, sv = sketches[eu], sketches[ev]
    if lane == LANE_REF:
        diff = kref.hamming_distance_ref(su, sv)
    else:
        diff = hamming_distance(pad_to(su, block, (0,)),
                                pad_to(sv, block, (0,)), be=block,
                                interpret=pol.interpret(lane))[:e0]
    return hamming_sigma(diff, samples)
