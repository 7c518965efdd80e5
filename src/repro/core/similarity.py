"""Exact structural-similarity computation (paper §4.1.1, Algorithm 1).

σ(u,v) is computed once per undirected edge by a **degree-bucketed
similarity engine**, and the half-edge (v,u) is given the same bits.
Real-world graphs are power-law: one hub vertex used to inflate the single
dense ``[n, Δ]`` padded neighbor matrix to O(n·Δ) memory and every edge
probe to O(Δ) work. The bucketed layout kills that skew
pathology (the GPUSCAN++ work-partitioning insight, applied to the padded
operand layout):

* **Degree classes** — vertices are partitioned into power-of-two
  *open-degree* classes (widths 8, 16, 32, …, capped at ``HUB_TILE``):
  the class width is the padded width of the vertex's open neighbor row,
  the operand the probe kernels actually scan (closed-neighborhood terms
  are added analytically in the epilogue). Each class materializes one
  fixed-shape padded block ``[K_c, w_c]`` whose row width is the *class*
  width, not the global max. Total operand memory is
  Σ_v pow2(deg v) ≤ 2·m2 + n·``BUCKET_FLOOR`` = **O(m + n)**.

* **Hub-row splitting** — a vertex wider than ``HUB_TILE`` (the storage
  tile width) occupies ⌈deg/``HUB_TILE``⌉ consecutive *tile rows* of the
  top block instead of forcing one giant row: a degree-10⁶ hub streams
  through the engine in 2048-wide tiles. Tiles are contiguous slices of
  the sorted neighbor row, so a per-chunk gather + reshape reassembles a
  sorted full-width row transiently (bounded by the chunk budget), never
  as a persistent giant block.

* **Edge routing** — each edge probes its **min-degree side** into its
  max-degree side: the probe row (width = the smaller class) is binary
  searched inside the target row (sorted ascending). Edges are grouped by
  (probe class, target class, tile counts) and each group runs through one
  fixed-shape jit kernel, so total similarity work is
  O(Σ_e min-side-degree · log max-side-degree). Kernel shapes are pure
  powers of two — the jit cache is shared across graphs, construction,
  the LSH exact-edge pass, and every incremental ``apply_delta`` batch.

* **σ bit-stability** — σ(u,v) depends only on the two endpoint rows,
  their class widths/tile counts, the endpoint norms and closed degrees.
  All of those are local: an edit batch perturbs them exactly for edges
  with a touched endpoint, so the incremental-update path
  (:mod:`repro.core.update`) carries every other σ bit-for-bit with *no*
  global-width fallback (the old "padded width changed → full re-sim"
  escape hatch is gone; only the affected degree classes re-run).

Entry points:

* ``compute_similarities`` — σ for every half-edge (production path).
* ``SimilarityPlan.half_edge_sims`` — σ for a set of half-edges closed
  under twins (all m2, or the §6.3 degree-heuristic exact subset under
  LSH): only the upper half-edges (u < v) run through the chunk programs,
  each lower one reads its twin's result (σ(u,v) and σ(v,u) are the same
  bits: one route, one probe, a commutative epilogue on equal weights).
* ``edge_similarities_subset`` / ``SimilarityPlan.edge_sims`` — σ for an
  arbitrary edge subset (the incremental-update frontier recompute),
  every edge computed. Group chunks are padded to power-of-two shapes so
  repeated calls reuse one compiled kernel per (class pair).
* ``SimilarityPlan`` — the bucketed operands for one graph (blocks, vertex
  routing tables, norms); build once via :func:`plan_for` and reuse.
  :meth:`SimilarityPlan.apply` derives the successor plan for an edited
  graph by patching only the affected degree-class blocks (see below), so
  the incremental-update path never rebuilds the O(m + n) operands.
* ``compute_similarities_dense`` — small-graph oracle: σ from the closed
  weighted adjacency product (W̄·W̄ᵀ) gathered at edges. The Pallas
  triangle kernel (repro.kernels.triangle_count) reproduces this product
  with blocked MXU tiles. For *unweighted* graphs every intermediate is a
  small integer, exact in float32 under any reduction order, so the
  bucketed engine is **bit-identical** to this oracle; weighted sums are
  order-sensitive at the ULP level (asserted in tests).

Per-group lane choice goes through :class:`repro.backend.ExecutionPolicy`
(``plan.edge_sims(..., policy=...)``): the ``ref`` lane is the jnp
searchsorted engine below, the Pallas lanes run the sorted-probe kernel
(:mod:`repro.kernels.bucket_probe`, the masked-gram pattern extended with
target-tile streaming) on the same gathered operands — on a TPU every
group runs the compiled kernel, elsewhere the ``ref`` lane, and
``REPRO_LANE`` pins a lane everywhere. All lanes are bit-identical on
unweighted σ (ULP on weighted), so lane choice never moves a
fingerprint.

Supported measures (paper §2.1/§4.1.1):
  * ``cosine``  — weighted cosine over closed neighborhoods (w(x,x)=1);
                  reduces to unweighted cosine when all weights are 1.
  * ``jaccard`` — Jaccard over closed neighborhoods (unweighted graphs).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend.padding import (
    np_log2 as _np_log2,
    np_pow2ceil as _np_pow2ceil,
    pad1 as _pad1,
    pow2_bucket as _pow2_bucket,
    pow2ceil as _pow2ceil,
)
from repro.backend.policy import (
    LANE_COMPILED, LANE_INTERPRET, LANE_REF, ExecutionPolicy, default_policy,
)
from repro.core.graph import CSRGraph, to_dense
from repro.obs.trace import maybe_span

MEASURES = ("cosine", "jaccard")

# smallest degree-class width: classes are 8, 16, 32, … (pow2)
BUCKET_FLOOR = 8
# storage tile width: rows wider than this split into HUB_TILE-wide tiles
HUB_TILE = 2048
# per-chunk element budget for the transient gathered row matrices
CHUNK_ELEMS = 1 << 22
# chunk programs the similarity pass keeps queued on the device: before
# dispatching chunk i it waits for chunk i - INFLIGHT_CHUNKS. A deeper
# window is no faster on a v5e: the runtime's own queue then blocks the
# dispatches instead, and the wait no longer shows as a wait
INFLIGHT_CHUNKS = 8
# fewest edges per compiled bucket_probe chunk: one full 128-lane row
KERNEL_LANE_EDGES = 128


def _routing_tables(deg: np.ndarray, n: int, hub_tile: int):
    """Degree → bucketing derivation: (widths, vclass, vtiles).

    The single source of truth shared by :meth:`SimilarityPlan.build` and
    :meth:`SimilarityPlan.apply` — their bit-identity contract requires
    one implementation of the class rule, not two that must be kept in
    sync by hand."""
    if not n:
        return (), np.zeros(0, np.int32), np.zeros(0, np.int32)
    w_full = 1 << np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
    w_full = np.maximum(w_full, BUCKET_FLOOR)
    w_cap = np.minimum(w_full, hub_tile)
    vtiles = np.where(w_full > hub_tile,
                      -(-deg // hub_tile), 1).astype(np.int32)
    widths = tuple(int(w) for w in np.unique(w_cap[:n]))
    vclass = np.searchsorted(widths, w_cap[:n]).astype(np.int32)
    return widths, vclass, vtiles


def closed_norms(g: CSRGraph) -> jax.Array:
    """sqrt(Σ_{x∈N̄(v)} w(v,x)²) with w(v,v)=1, float32[n]."""
    sq = jax.ops.segment_sum(g.wgts**2, g.edge_u, num_segments=g.n)
    return jnp.sqrt(sq + 1.0)


# ---------------------------------------------------------------------------
# degree-bucketed plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SimilarityPlan:
    """Bucketed similarity operands for one graph.

    Per degree class ``c``: a padded block ``nbr_blocks[c]`` int32[K_c, w_c]
    / ``wgt_blocks[c]`` float32[K_c, w_c] whose rows are `HUB_TILE`-capped
    tiles of sorted open-neighbor rows (pad id = n, sorts last; the final
    block row is an all-pad sentinel and K_c is rounded up to a power of
    two so block shapes — and therefore compiled kernels — are stable
    under small graph edits). Vertex routing tables (host numpy):
    ``vclass`` (class id), ``vrow`` (first tile row), ``vtiles`` (tile
    count; 1 unless the vertex is a hub).
    """

    n: int
    m2: int
    hub_tile: int
    widths: Tuple[int, ...]
    nbr_blocks: Tuple[jax.Array, ...]
    wgt_blocks: Tuple[jax.Array, ...]
    vclass: np.ndarray   # int32[n]
    vrow: np.ndarray     # int32[n]
    vtiles: np.ndarray   # int32[n]
    deg: np.ndarray      # int64[n] open degrees (host routing key)
    norms: jax.Array     # float32[n]
    cdeg: jax.Array      # int32[n]
    # observability: the kernel groups the most recent edge_sims call routed
    # to, each as (probe width, target width, lane, edges) (stat slot, not
    # identity; written via object.__setattr__)
    last_group_shapes: Tuple[Tuple[int, int, str, int], ...] = ()
    # observability: what the :meth:`apply` that produced this plan did
    # (None for plans built from scratch)
    last_apply: Optional[dict] = None

    @property
    def last_groups(self) -> int:
        """Kernel groups the most recent edge_sims call routed to."""
        return len(self.last_group_shapes)

    # -- construction -------------------------------------------------------
    @staticmethod
    def build(g: CSRGraph,
              hub_tile: Optional[int] = None) -> "SimilarityPlan":
        if hub_tile is None:
            hub_tile = default_policy().profile.hub_tile
        deg = np.diff(np.asarray(g.offsets)).astype(np.int64)
        n = g.n
        widths, vclass, vtiles = _routing_tables(deg, n, hub_tile)

        offsets = np.asarray(g.offsets)
        eu = np.asarray(g.edge_u) if g.m2 else np.zeros(0, np.int64)
        nbrs = np.asarray(g.nbrs) if g.m2 else np.zeros(0, np.int32)
        wgts = np.asarray(g.wgts) if g.m2 else np.zeros(0, np.float32)
        pos = (np.arange(g.m2, dtype=np.int64) - offsets[eu]) if g.m2 \
            else np.zeros(0, np.int64)

        vrow = np.zeros(n, dtype=np.int32)
        nbr_blocks: List[jax.Array] = []
        wgt_blocks: List[jax.Array] = []
        for ci, w in enumerate(widths):
            members = np.flatnonzero(vclass == ci)
            tiles = vtiles[members].astype(np.int64)
            starts = np.concatenate([[0], np.cumsum(tiles)[:-1]])
            vrow[members] = starts
            k_rows = int(tiles.sum())
            # sentinel pad row at the end; round rows to pow2 for jit-cache
            # stability across incremental graph edits
            k_pad = _pow2ceil(k_rows + 1)
            nb = np.full((k_pad, w), n, dtype=np.int32)
            wb = np.zeros((k_pad, w), dtype=np.float32)
            if g.m2:
                sel = np.flatnonzero(vclass[eu] == ci)
                if len(sel):
                    p = pos[sel]
                    r = vrow[eu[sel]] + (p // w)
                    c = p % w
                    nb[r, c] = nbrs[sel]
                    wb[r, c] = wgts[sel]
            nbr_blocks.append(jnp.asarray(nb))
            wgt_blocks.append(jnp.asarray(wb))

        return SimilarityPlan(
            n=n, m2=g.m2, hub_tile=hub_tile, widths=widths,
            nbr_blocks=tuple(nbr_blocks), wgt_blocks=tuple(wgt_blocks),
            vclass=vclass, vrow=vrow, vtiles=vtiles, deg=deg,
            norms=closed_norms(g), cdeg=g.closed_degrees())

    # -- incremental maintenance -------------------------------------------
    def apply(self, g2: CSRGraph, touched) -> "SimilarityPlan":
        """Successor plan for an edited graph, patching only affected blocks.

        ``g2`` is the post-edit graph (same vertex set as this plan's
        graph); ``touched`` holds every vertex whose open neighbor row —
        content or weights — changed (a superset is correct, a subset is
        not). The result is **bit-identical** to
        ``SimilarityPlan.build(g2, self.hub_tile)`` (asserted by the
        edit-script oracle), but the per-batch block work is proportional
        to the *touched* rows/classes, never O(m):

        * a degree class with no touched member and an unchanged layout
          reuses its device blocks outright (``reused``);
        * touched rows of a layout-stable class re-pack in place — one
          scatter of the rewritten tile rows (``patched``);
        * a membership/tile-count change (a vertex migrating between its
          two pow2 classes, a hub splitting or merging tile rows under the
          ``HUB_TILE`` rule) re-derives the block by gathering kept rows
          from the old block and scattering the rewritten ones
          (``remapped``);
        * a class width with no predecessor block packs fresh — all its
          members are touched by construction (``built``).

        Vertex routing tables are recomputed host-side in O(n) (exactly as
        :meth:`build` does, so class ids / row starts match bit-for-bit),
        norms are patched only at ``touched`` via a frontier-restricted
        segment sum, and ``last_apply`` on the returned plan reports the
        work counters (``rows_written`` is the acceptance counter: block
        tile rows actually rewritten this batch).
        """
        if g2.n != self.n:
            raise ValueError(
                f"plan.apply: vertex count changed ({self.n} -> {g2.n}); "
                "incremental maintenance assumes a fixed vertex set")
        n = self.n
        hub_tile = self.hub_tile
        touched = np.asarray(touched, dtype=np.int64)
        tmask = np.zeros(n, dtype=bool)
        tmask[touched] = True

        off2 = np.asarray(g2.offsets)
        deg2 = np.diff(off2).astype(np.int64)
        # routing tables via the same derivation build() uses
        widths2, vclass2, vtiles2 = _routing_tables(deg2, n, hub_tile)

        nbrs2 = np.asarray(g2.nbrs) if g2.m2 else np.zeros(0, np.int32)
        wgts2 = np.asarray(g2.wgts) if g2.m2 else np.zeros(0, np.float32)
        old_ci_of_width = {w: i for i, w in enumerate(self.widths)}

        stats = {"classes": len(widths2), "reused": 0, "patched": 0,
                 "remapped": 0, "built": 0, "rows_written": 0}
        vrow2 = np.zeros(n, dtype=np.int32)
        nbr_blocks: List[jax.Array] = []
        wgt_blocks: List[jax.Array] = []
        for ci, w in enumerate(widths2):
            members = np.flatnonzero(vclass2 == ci)
            tiles = vtiles2[members].astype(np.int64)
            starts = np.concatenate([[0], np.cumsum(tiles)[:-1]])
            vrow2[members] = starts
            k_rows = int(tiles.sum())
            k_pad = _pow2ceil(k_rows + 1)
            oci = old_ci_of_width.get(w)
            rewrite = members[tmask[members]]

            if oci is None:
                # brand-new width: every member changed degree => touched
                nb = np.full((k_pad, w), n, dtype=np.int32)
                wb = np.zeros((k_pad, w), dtype=np.float32)
                rows, valn, valw = _member_tile_rows(
                    members, w, vrow2, vtiles2, off2, nbrs2, wgts2, n)
                nb[rows] = valn
                wb[rows] = valw
                stats["built"] += 1
                stats["rows_written"] += len(rows)
                nbr_blocks.append(jnp.asarray(nb))
                wgt_blocks.append(jnp.asarray(wb))
                continue

            old_nb = self.nbr_blocks[oci]
            old_wb = self.wgt_blocks[oci]
            members1 = np.flatnonzero(self.vclass == oci)
            stable = (old_nb.shape[0] == k_pad
                      and len(members1) == len(members)
                      and np.array_equal(members1, members)
                      and np.array_equal(self.vtiles[members],
                                         vtiles2[members]))
            if stable and len(rewrite) == 0:
                nbr_blocks.append(old_nb)
                wgt_blocks.append(old_wb)
                stats["reused"] += 1
                continue

            rows, valn, valw = _member_tile_rows(
                rewrite, w, vrow2, vtiles2, off2, nbrs2, wgts2, n)
            stats["rows_written"] += len(rows)
            # pad the scatter to a pow2 row count aimed at the sentinel row
            # (kept all-pad by writing pad content), so repeated batches hit
            # one compiled scatter per block shape
            r_pad = _pow2ceil(len(rows)) - len(rows)
            if r_pad:
                rows = np.concatenate(
                    [rows, np.full(r_pad, k_pad - 1, np.int32)])
                valn = np.concatenate(
                    [valn, np.full((r_pad, w), n, np.int32)])
                valw = np.concatenate(
                    [valw, np.zeros((r_pad, w), np.float32)])
            if stable:
                nb, wb = _patch_block(
                    old_nb, old_wb, jnp.asarray(rows),
                    jnp.asarray(valn), jnp.asarray(valw))
                stats["patched"] += 1
            else:
                # layout moved: gather kept members' rows from the old
                # block (they are bit-identical), then scatter the rest
                kept = members[~tmask[members]]
                src = np.full(k_pad, old_nb.shape[0] - 1, dtype=np.int32)
                if len(kept):
                    kt = vtiles2[kept].astype(np.int64)   # == old tiles
                    new_r = _expand_tile_rows(vrow2[kept], kt)
                    old_r = _expand_tile_rows(self.vrow[kept], kt)
                    src[new_r] = old_r
                nb, wb = _remap_block(
                    old_nb, old_wb, jnp.asarray(src), jnp.asarray(rows),
                    jnp.asarray(valn), jnp.asarray(valw))
                stats["remapped"] += 1
            nbr_blocks.append(nb)
            wgt_blocks.append(wb)

        # norms change exactly at touched vertices; the restricted segment
        # sum walks each touched row in CSR order — the same value sequence
        # the full closed_norms reduction uses, so patched entries are
        # bit-identical to a from-scratch build (oracle-asserted)
        if len(touched) and g2.m2:
            sel = tmask[np.asarray(g2.edge_u)]
            sq = jax.ops.segment_sum(
                jnp.asarray(wgts2[sel]) ** 2,
                jnp.asarray(np.asarray(g2.edge_u)[sel]),
                num_segments=n)
            t = jnp.asarray(touched)
            norms2 = self.norms.at[t].set(jnp.sqrt(sq + 1.0)[t])
        elif len(touched):
            norms2 = self.norms.at[jnp.asarray(touched)].set(1.0)
        else:
            norms2 = self.norms

        return SimilarityPlan(
            n=n, m2=g2.m2, hub_tile=hub_tile, widths=widths2,
            nbr_blocks=tuple(nbr_blocks), wgt_blocks=tuple(wgt_blocks),
            vclass=vclass2, vrow=vrow2, vtiles=vtiles2, deg=deg2,
            norms=norms2, cdeg=g2.closed_degrees(), last_apply=stats)

    # -- introspection ------------------------------------------------------
    def operand_bytes(self) -> int:
        """Persistent similarity-operand footprint (neighbor + weight
        blocks + norms + closed degrees) in bytes — O(m + n)."""
        total = sum(int(np.prod(b.shape)) * (4 + 4) for b in self.nbr_blocks)
        return total + 8 * self.n

    def route(self, eu: np.ndarray, ev: np.ndarray):
        """Host-side routing: probe side (min (deg, id)) per edge and the
        per-edge group key (probe class, probe tiles^, target class,
        target tiles^). Returns (pu, pv, keys) with keys int64[m]."""
        du, dv = self.deg[eu], self.deg[ev]
        swap = (dv < du) | ((dv == du) & (ev < eu))
        pu = np.where(swap, ev, eu)
        pv = np.where(swap, eu, ev)
        sp = _np_pow2ceil(self.vtiles[pu])
        st = _np_pow2ceil(self.vtiles[pv])
        keys = (((self.vclass[pu].astype(np.int64) * 64
                  + _np_log2(sp)) * 64
                 + self.vclass[pv]) * 64 + _np_log2(st))
        return pu, pv, keys

    def group_count(self, eu: np.ndarray, ev: np.ndarray) -> int:
        """Number of distinct (class-pair, tile-shape) kernel groups an
        edge subset routes to (observability for apply_delta)."""
        if len(eu) == 0:
            return 0
        _, _, keys = self.route(np.asarray(eu, np.int64),
                                np.asarray(ev, np.int64))
        return len(np.unique(keys))

    def _stage(self, idx, pu, pv, eu, ev, ew, cp: int, ct: int,
               rows: int) -> np.ndarray:
        """A group's chunk operands, int32[7, rows]: probe first tile row
        and tile count, target first tile row and tile count, the edge's
        endpoints and its weight (bit-cast). Slots past the group's edges
        probe the sentinel rows with weight 0."""
        staged = np.empty((7, rows), np.int32)
        k = len(idx)
        staged[0, :k] = self.vrow[pu[idx]]
        staged[1, :k] = self.vtiles[pu[idx]]
        staged[2, :k] = self.vrow[pv[idx]]
        staged[3, :k] = self.vtiles[pv[idx]]
        staged[4, :k] = eu[idx]
        staged[5, :k] = ev[idx]
        staged[6, :k] = ew[idx].view(np.int32)
        staged[:, k:] = np.array(
            [self.nbr_blocks[cp].shape[0] - 1, 0,
             self.nbr_blocks[ct].shape[0] - 1, 0, 0, 0, 0], np.int32)[:, None]
        return staged

    # -- the engine ---------------------------------------------------------
    def edge_sims(
        self,
        eu,
        ev,
        ew,
        measure: str = "cosine",
        chunk: int = 1 << 16,
        policy: Optional[ExecutionPolicy] = None,
        tracer=None,
    ) -> jax.Array:
        """σ (or triangle counts with measure='_count') for an edge subset.

        Each (class pair, tile shape) group resolves its lane through the
        execution policy: ``ref`` runs the jnp searchsorted kernel, the
        Pallas lanes run the sorted-probe kernel on identical gathered
        operands (bit-identical on unweighted σ, ULP on weighted). Lane
        decisions count under ``backend.lane.bucket_probe.<lane>``.

        Each group's operands go to the device in one copy; its chunks
        slice them there and queue without a host round trip, at most
        ``INFLIGHT_CHUNKS`` in flight. σ stays on the device: the result
        is gathered into input order from the chunks' slots.

        With a ``tracer`` (:class:`repro.obs.Tracer`) the host routing is
        a ``similarity.route`` span and each group a ``similarity.group``
        span (``pe``, ``te``, ``lane``, ``edges``, ``chunks``); chunks get
        no span of their own, only the window's waits on the device are
        timed, summed into one ``similarity.device_wait`` event per group,
        counted under ``similarity.chunks``, and those dispatched before
        the previous chunk was done under ``similarity.chunks_queued``.
        The edges a chunk program computed count under
        ``similarity.sigma_computed``, those a Pallas lane computed also
        under ``similarity.sigma_kernel``. Without a tracer nothing is
        timed or counted.
        """
        if measure not in MEASURES + ("_count",):
            raise ValueError(f"measure must be one of {MEASURES}")
        pol = policy if policy is not None else default_policy()
        eu = np.asarray(eu, dtype=np.int64)
        ev = np.asarray(ev, dtype=np.int64)
        ew = np.asarray(ew, dtype=np.float32)
        total = len(eu)
        out_dt = np.int32 if measure == "_count" else np.float32
        if total == 0:
            return jnp.zeros((0,), out_dt)

        with maybe_span(tracer, "similarity.route", edges=total):
            pu, pv, keys = self.route(eu, ev)
            order = np.argsort(keys, kind="stable")
            bounds = np.flatnonzero(np.diff(keys[order])) + 1
            groups = np.split(order, bounds)

        shapes, runs, nslots, kernel = [], [], 0, 0
        for idx in groups:
            cp = int(self.vclass[pu[idx[0]]])
            ct = int(self.vclass[pv[idx[0]]])
            sp = _pow2ceil(int(self.vtiles[pu[idx[0]]]))
            st = _pow2ceil(int(self.vtiles[pv[idx[0]]]))
            pe = sp * self.widths[cp]
            te = st * self.widths[ct]
            lane = pol.lane("bucket_probe")
            pol.note("bucket_probe", lane)
            shapes.append((pe, te, lane, len(idx)))
            if lane != LANE_REF:
                kernel += len(idx)
            cap = max(CHUNK_ELEMS // max(pe + te, 1), 1)
            cap = 1 << (cap.bit_length() - 1)
            if lane == LANE_COMPILED:
                # the kernel lays edges on the 128 lanes: a narrower chunk
                # would leave lanes idle on the widest (hub) groups
                cap = max(cap, KERNEL_LANE_EDGES)
            csize = min(_pow2_bucket(len(idx)), max(min(chunk, cap), 1))
            if lane == LANE_INTERPRET:
                # interpret-mode grids unroll at trace time: bound the
                # chunk so compile cost stays proportional to the profile
                csize = min(csize, _pow2ceil(
                    pol.profile.probe_interpret_chunk))
            chunks = -(-len(idx) // csize)
            runs.append((idx, cp, ct, sp, st, pe, te, lane, csize, chunks))
            nslots += chunks * csize

        # every chunk's result lands in its slots of one device buffer, in
        # group order; one gather puts σ in input order at the end
        slots = jnp.zeros(_pow2_bucket(nslots), out_dt)
        where = np.empty(total, np.int32)
        inflight = collections.deque()
        base = 0
        for idx, cp, ct, sp, st, pe, te, lane, csize, chunks in runs:
            wait, queued = 0.0, 0
            with maybe_span(tracer, "similarity.group", pe=pe, te=te,
                            lane=lane, edges=len(idx), chunks=chunks):
                # a pow2 count of chunk slots keeps the slicing program's
                # shapes in the same classes as the chunk programs'
                staged = jnp.asarray(self._stage(
                    idx, pu, pv, eu, ev, ew, cp, ct,
                    _pow2ceil(chunks) * csize))
                where[idx] = base + np.arange(len(idx), dtype=np.int32)
                for c in range(chunks):
                    if len(inflight) == INFLIGHT_CHUNKS:
                        # bound the queue: wait for the chunk dispatched
                        # INFLIGHT_CHUNKS before this one, never copy
                        oldest = inflight.popleft()
                        if tracer is None:
                            oldest.block_until_ready()
                        else:
                            tw = time.perf_counter()
                            oldest.block_until_ready()
                            wait += time.perf_counter() - tw
                    if tracer is not None and inflight:
                        queued += not inflight[-1].is_ready()
                    operands = (
                        _chunk_edges(staged, c * csize, size=csize),
                        self.nbr_blocks[cp], self.wgt_blocks[cp],
                        self.nbr_blocks[ct], self.wgt_blocks[ct],
                        self.norms, self.cdeg,
                    )
                    if lane == LANE_REF:
                        res = _bucket_sims_chunk(
                            *operands, sp=sp, st=st, measure=measure)
                    else:
                        res = _bucket_sims_chunk_pallas(
                            *operands, sp=sp, st=st, measure=measure,
                            be=min(pol.profile.probe_be, csize),
                            bt=pol.profile.probe_bt,
                            interpret=pol.interpret(lane))
                    slots = _place_chunk(slots, res, base + c * csize)
                    inflight.append(res)
            base += chunks * csize
            if tracer is not None:
                tracer.event("similarity.device_wait", wait)
                tracer.inc("similarity.chunks", chunks)
                tracer.inc("similarity.chunks_queued", queued)
        if tracer is not None:
            tracer.inc("similarity.sigma_computed", total)
            tracer.inc("similarity.sigma_kernel", kernel)
        object.__setattr__(self, "last_group_shapes", tuple(shapes))
        return _in_input_order(slots, jnp.asarray(where))

    def half_edge_sims(
        self,
        g: CSRGraph,
        sel=None,
        measure: str = "cosine",
        chunk: int = 1 << 16,
        policy: Optional[ExecutionPolicy] = None,
        tracer=None,
    ) -> jax.Array:
        """σ for the half-edges ``sel`` of this plan's graph ``g``, in
        ``sel``'s order: ``sel`` is a sorted index array into ``g``'s CSR
        slots (``None``: all m2) that holds the twin (v, u) of each of its
        half-edges (u, v).

        σ(u, v) and σ(v, u) are the same bits: both route to one probe
        side, one target side and one group, the weights of twins are
        equal, and the epilogue's product of norms and sum of closed
        degrees commute. So only the upper half-edges (u < v) go through
        :meth:`edge_sims`; each lower one reads its twin's result in one
        gather, its twin found by a stable device sort of the upper
        half-edges by their larger endpoint. A set in which not exactly
        half the half-edges are upper is not closed under twins and
        raises ``ValueError``; the check is necessary, not sufficient, so
        callers pass sets closed by construction.

        With a ``tracer`` the pass is traced as in :meth:`edge_sims`, and
        the half-edges given their twin's σ count under
        ``similarity.sigma_twinned``."""
        if measure not in MEASURES:
            raise ValueError(f"measure must be one of {MEASURES}")
        eu, ev, ew = (np.asarray(a) for a in (g.edge_u, g.nbrs, g.wgts))
        if sel is not None:
            sel = np.asarray(sel, dtype=np.int64)
            eu, ev, ew = eu[sel], ev[sel], ew[sel]
        total = len(eu)
        up = np.flatnonzero(eu < ev)
        if 2 * len(up) != total:
            raise ValueError(
                f"half_edge_sims: {len(up)} of {total} half-edges have "
                "u < v; a set closed under twins has exactly half")
        if total == 0:
            return jnp.zeros((0,), jnp.float32)
        sims = self.edge_sims(eu[up], ev[up], ew[up], measure, chunk,
                              policy, tracer)
        if tracer is not None:
            tracer.inc("similarity.sigma_twinned", total - len(up))
        return _twinned_order(sims, jnp.asarray(ev[up], dtype=jnp.int32),
                              jnp.asarray(eu < ev))


def _expand_tile_rows(first: np.ndarray, tiles: np.ndarray) -> np.ndarray:
    """Concatenated [first_i, first_i + tiles_i) tile-row ranges, int32."""
    total = int(tiles.sum())
    if total == 0:
        return np.zeros(0, np.int32)
    ends = np.cumsum(tiles)
    within = np.arange(total, dtype=np.int64) - np.repeat(ends - tiles, tiles)
    return (np.repeat(np.asarray(first, np.int64), tiles)
            + within).astype(np.int32)


def _member_tile_rows(members, w, vrow, vtiles, offsets, nbrs, wgts, n):
    """Packed tile rows for a member set: (rows int32[R], nbr int32[R, w],
    wgt float32[R, w]) where R = Σ tiles — each member's sorted CSR row
    split into ``w``-wide tiles, padded with id ``n`` / weight 0."""
    members = np.asarray(members, np.int64)
    tiles = vtiles[members].astype(np.int64)
    rows = _expand_tile_rows(vrow[members], tiles)
    valn = np.full((len(rows), w), n, dtype=np.int32)
    valw = np.zeros((len(rows), w), dtype=np.float32)
    if len(members):
        degs = offsets[members + 1].astype(np.int64) - offsets[members]
        tot = int(degs.sum())
        if tot:
            ends = np.cumsum(degs)
            pos = np.arange(tot, dtype=np.int64) - np.repeat(
                ends - degs, degs)
            src = np.repeat(offsets[members].astype(np.int64), degs) + pos
            row_base = np.repeat(np.cumsum(tiles) - tiles, degs)
            r = row_base + pos // w
            c = pos % w
            valn[r, c] = nbrs[src]
            valw[r, c] = wgts[src]
    return rows, valn, valw


@jax.jit
def _patch_block(nb, wb, rows, valn, valw):
    """Scatter rewritten tile rows into a layout-stable block (functional
    update — the old block stays intact for the predecessor plan)."""
    return nb.at[rows].set(valn), wb.at[rows].set(valw)


@jax.jit
def _remap_block(nb, wb, src, rows, valn, valw):
    """Gather kept rows from the old block per ``src`` (sentinel index for
    vacated rows — all-pad, like a fresh block), then scatter rewrites."""
    return nb[src].at[rows].set(valn), wb[src].at[rows].set(valw)


def _gather_tiled_rows(block_n, block_w, first, cnt, s: int):
    """Reassemble [c, s·w] sorted rows from ``s`` consecutive tile rows per
    entry (hub-row splitting: tiles beyond ``cnt`` map to the all-pad
    sentinel row, which sorts last)."""
    k_sent = block_n.shape[0] - 1
    w = block_n.shape[1]
    t = jnp.arange(s, dtype=jnp.int32)[None, :]
    idx = jnp.where(t < cnt[:, None], first[:, None] + t, k_sent)
    c = first.shape[0]
    return (block_n[idx].reshape(c, s * w), block_w[idx].reshape(c, s * w))


def _sigma_epilogue(shared_dot, shared_cnt, eu, ev, ew, norms, cdeg,
                    measure: str):
    """Shared-dot/count → σ. One implementation for every engine lane
    (jnp searchsorted, Pallas probe, shard_map): the bit-identity contract
    requires the epilogue arithmetic to exist exactly once."""
    if measure == "_count":
        return shared_cnt.astype(jnp.int32)
    if measure == "cosine":
        # closed-neighborhood dot: open shared dot + x=u and x=v terms
        closed_dot = shared_dot + 2.0 * ew
        return closed_dot / (norms[eu] * norms[ev])
    elif measure == "jaccard":
        c = shared_cnt.astype(jnp.float32) + 2.0       # + {u, v}
        union = cdeg[eu].astype(jnp.float32) + cdeg[ev].astype(jnp.float32) - c
        return c / union
    raise ValueError(f"unknown measure {measure!r}")


@functools.partial(jax.jit, static_argnames=("size",))
def _chunk_edges(staged, at, *, size: int):
    """One chunk's int32[7, size] edge block, sliced on the device from its
    group's staged int32[7, L] block (:meth:`SimilarityPlan._stage`)."""
    return jax.lax.dynamic_slice_in_dim(staged, at, size, axis=1)


def _edge_operands(edges):
    """(p0, pt, t0, tt, eu, ev, ew) of a chunk's int32[7, c] edge block;
    the weights ride bit-cast. One output buffer per slice instead of
    seven: each costs the host an allocation per chunk."""
    return (*edges[:6], jax.lax.bitcast_convert_type(edges[6], jnp.float32))


@functools.partial(jax.jit, donate_argnums=0)
def _place_chunk(slots, res, at):
    """Write one chunk's results into the pass's slot buffer at ``at``."""
    return jax.lax.dynamic_update_slice_in_dim(slots, res, at, axis=0)


@jax.jit
def _in_input_order(slots, where):
    """σ in input order: edge i's result sits in slot ``where[i]``."""
    return slots[where]


@jax.jit
def _twinned_order(sims, ev_up, upper):
    """σ of a set of half-edges closed under twins, in its order, from
    ``sims``, σ of its upper half-edges (``upper``: u < v) in their order.
    In order, the lower half-edges (u, v) list their twins (v, u) by
    (u, v): the upper half-edges sorted stably by their larger endpoint
    ``ev_up``, one device sort of half the set."""
    twin = jnp.argsort(ev_up, stable=True)
    k_up = jnp.cumsum(upper) - 1
    k_low = jnp.maximum(jnp.cumsum(~upper) - 1, 0)
    return sims[jnp.where(upper, k_up, twin[k_low])]


def _bucket_sims_core(p0, pt, t0, tt, eu, ev, ew,
                      p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                      sp: int, st: int, measure: str):
    """Sorted-probe body for one (probe class, target class) group chunk.

    Shared between the jitted single-host kernel and the shard_map path in
    :mod:`repro.core.distributed`. This is the ``ref`` lane of the
    ``bucket_probe`` op: the jnp searchsorted engine.
    """
    n = norms.shape[0]
    rows_p, w_p = _gather_tiled_rows(p_nbr, p_wgt, p0, pt, sp)
    rows_t, w_t = _gather_tiled_rows(t_nbr, t_wgt, t0, tt, st)

    pos = jax.vmap(jnp.searchsorted)(rows_t, rows_p)
    pos_c = jnp.minimum(pos, rows_t.shape[1] - 1)
    hit = jnp.take_along_axis(rows_t, pos_c, axis=1) == rows_p
    hit &= rows_p < n                                  # mask probe padding
    w_match = jnp.take_along_axis(w_t, pos_c, axis=1)
    shared_dot = jnp.sum(jnp.where(hit, w_p * w_match, 0.0), axis=1)
    shared_cnt = jnp.sum(hit, axis=1)
    return _sigma_epilogue(shared_dot, shared_cnt, eu, ev, ew, norms, cdeg,
                           measure)


@functools.partial(jax.jit, static_argnames=("sp", "st", "measure"))
def _bucket_sims_chunk(edges, p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                       *, sp: int, st: int, measure: str):
    """One fixed-shape per-(bucket_u, bucket_v) kernel invocation. Every
    shape in the signature is a power of two, so the jit cache is shared
    across graphs and across repeated ``apply_delta`` batches."""
    return _bucket_sims_core(*_edge_operands(edges),
                             p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                             sp, st, measure)


def _bucket_sims_pallas_core(p0, pt, t0, tt, eu, ev, ew,
                             p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                             sp: int, st: int, measure: str,
                             be: int, bt: int, interpret: bool):
    """The Pallas lane of one group chunk: gather the same tiled rows the
    jnp engine would, run the sorted-probe kernel
    (:mod:`repro.kernels.bucket_probe`) instead of searchsorted, and apply
    the shared epilogue. Unweighted shared dots/counts are small integers
    (exact in f32 under any accumulation order), so this lane is
    bit-identical to :func:`_bucket_sims_core`; weighted dots agree to
    ULP. Shared with the shard_map path in :mod:`repro.core.distributed`."""
    from repro.kernels.bucket_probe import bucket_probe
    from repro.kernels.ops import probe_operands

    n = norms.shape[0]
    rows_p, w_p = _gather_tiled_rows(p_nbr, p_wgt, p0, pt, sp)
    rows_t, w_t = _gather_tiled_rows(t_nbr, t_wgt, t0, tt, st)
    ids_p, w_p, ids_t, w_t, bt = probe_operands(
        rows_p, w_p, rows_t, w_t, n, be, bt)
    dot, cnt = bucket_probe(ids_p, w_p, ids_t, w_t, be=be, bt=bt,
                            interpret=interpret)
    e0 = eu.shape[0]
    return _sigma_epilogue(dot[:e0], cnt[:e0], eu, ev, ew, norms, cdeg,
                           measure)


@functools.partial(jax.jit, static_argnames=(
    "sp", "st", "measure", "be", "bt", "interpret"))
def _bucket_sims_chunk_pallas(edges, p_nbr, p_wgt, t_nbr, t_wgt, norms,
                              cdeg, *, sp: int, st: int, measure: str,
                              be: int, bt: int, interpret: bool):
    """Jitted :func:`_bucket_sims_pallas_core` for one group chunk."""
    return _bucket_sims_pallas_core(*_edge_operands(edges),
                                    p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                                    sp, st, measure, be, bt, interpret)


# ---------------------------------------------------------------------------
# plan cache (one plan per live graph object)
# ---------------------------------------------------------------------------
_PLAN_CACHE: Dict[Tuple[int, int], Tuple[object, SimilarityPlan]] = {}


def _evict_plan(key, ref) -> None:
    """Finalizer: drop a cache entry when its graph dies — but only if the
    slot still belongs to that graph (ids are reused, so a delayed
    finalizer must never pop a successor's entry)."""
    ent = _PLAN_CACHE.get(key)
    if ent is not None and ent[0] is ref:
        del _PLAN_CACHE[key]


def _cache_plan(g: CSRGraph, key, plan: SimilarityPlan) -> None:
    ref = weakref.ref(g)
    _PLAN_CACHE[key] = (ref, plan)
    # evict the moment the graph is collected: a dead graph's O(m + n)
    # device blocks must not squat in the cache until the next miss sweeps
    weakref.finalize(g, _evict_plan, key, ref)


def plan_for(g: CSRGraph,
             hub_tile: Optional[int] = None, *,
             tracer=None) -> SimilarityPlan:
    """The bucketed :class:`SimilarityPlan` for ``g``, cached per live graph
    object so construction, the LSH exact pass, triangle counting, and the
    incremental-update path share one set of device blocks. Entries are
    evicted by a ``weakref.finalize`` on the graph, so a plan never
    outlives its graph. ``hub_tile`` defaults to the active execution
    policy's autotune profile (legacy constant ``HUB_TILE`` when
    untuned). With a ``tracer``, a cache miss's build is a
    ``similarity.plan`` span."""
    if hub_tile is None:
        hub_tile = default_policy().profile.hub_tile
    key = (id(g), hub_tile)
    ent = _PLAN_CACHE.get(key)
    if ent is not None and ent[0]() is g:
        return ent[1]
    with maybe_span(tracer, "similarity.plan", n=g.n, m2=g.m2):
        plan = SimilarityPlan.build(g, hub_tile)
    _cache_plan(g, key, plan)
    return plan


def adopt_plan(g: CSRGraph, plan: SimilarityPlan) -> SimilarityPlan:
    """Seed the cache with an externally derived plan for ``g`` (the
    incremental-update path hands over :meth:`SimilarityPlan.apply`'s
    successor so the post-edit graph never triggers an O(m) rebuild)."""
    _cache_plan(g, (id(g), plan.hub_tile), plan)
    return plan


def cached_plan(g: CSRGraph,
                hub_tile: Optional[int] = None) -> Optional[SimilarityPlan]:
    """The cached plan for ``g`` if one exists (None otherwise; never
    builds). Lets tests distinguish a maintained plan from a fresh one."""
    if hub_tile is None:
        hub_tile = default_policy().profile.hub_tile
    ent = _PLAN_CACHE.get((id(g), hub_tile))
    if ent is not None and ent[0]() is g:
        return ent[1]
    return None


def plan_cache_size() -> int:
    """Live entry count of the per-graph plan cache (leak detection)."""
    return len(_PLAN_CACHE)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def edge_similarities_subset(
    g: CSRGraph,
    eu: jax.Array,
    ev: jax.Array,
    ew: jax.Array,
    measure: str = "cosine",
    chunk: int = 1 << 16,
    *,
    tracer=None,
) -> jax.Array:
    """Exact σ for an arbitrary subset of edges (endpoint arrays), every
    edge computed (a set closed under twins takes
    :meth:`SimilarityPlan.half_edge_sims`, which computes half). Edges
    route to per-degree-class kernels with power-of-two chunk shapes, so
    repeated calls (e.g. update batches at the same pow2 size) reuse one
    compiled function per class pair. ``tracer`` traces the
    plan build and the pass (:meth:`SimilarityPlan.edge_sims`).
    """
    if measure not in MEASURES:
        raise ValueError(f"measure must be one of {MEASURES}")
    return plan_for(g, tracer=tracer).edge_sims(eu, ev, ew, measure, chunk,
                                                tracer=tracer)


def compute_similarities(
    g: CSRGraph, measure: str = "cosine", chunk: int = 1 << 16, *,
    tracer=None,
) -> jax.Array:
    """Exact σ for every half-edge, float32[m2]: host-orchestrated routing
    over the degree-bucketed engine, σ computed once per undirected edge
    (:meth:`SimilarityPlan.half_edge_sims`) and given to both halves.
    ``tracer`` traces the plan build and the pass."""
    return plan_for(g, tracer=tracer).half_edge_sims(
        g, None, measure, chunk, tracer=tracer)


def triangle_counts(g: CSRGraph) -> jax.Array:
    """|N(u) ∩ N(v)| per half-edge (the paper's triangle-counting
    primitive), via the bucketed sorted-probe engine."""
    return plan_for(g).edge_sims(g.edge_u, g.nbrs, g.wgts, "_count")


# ---------------------------------------------------------------------------
# small-graph dense oracle
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("measure",))
def _dense_sims(adj_c, eu, ev, cdeg, measure):
    prod = adj_c @ adj_c.T
    dots = prod[eu, ev]
    if measure == "cosine":
        norms = jnp.sqrt(jnp.diag(prod))
        return dots / (norms[eu] * norms[ev])
    union = cdeg[eu].astype(jnp.float32) + cdeg[ev].astype(jnp.float32) - dots
    return dots / union


def compute_similarities_dense(g: CSRGraph, measure: str = "cosine") -> jax.Array:
    """Small-graph oracle via the closed adjacency product."""
    weighted = measure == "cosine"
    adj_c = to_dense(g, closed=True, weighted=weighted)
    return _dense_sims(adj_c, g.edge_u, g.nbrs, g.closed_degrees(), measure)

