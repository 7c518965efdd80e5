"""Incremental GS*-Index maintenance (beyond-paper: dynamic graphs).

The paper's premise is that the index is built once and amortized over many
(μ, ε) queries — but serving workloads mutate the graph under the queries.
``apply_delta`` maintains an existing :class:`ScanIndex` under a batch of
edge inserts/deletes. The expensive parts of construction — the bucketed
similarity pass, its O(m + n) operand build, and the O(m log m) device
sorts — all shrink to the *touched* structure: σ recomputes only on the
frontier (edges incident to touched endpoints), and the degree-bucketed
``SimilarityPlan`` is itself **maintained incrementally**
(:meth:`repro.core.similarity.SimilarityPlan.apply`: touched rows re-pack
in place, class migrations move a vertex between exactly two blocks, hub
rows split/merge under the ``HUB_TILE`` rule, untouched blocks are reused
outright). What remains per batch is O(m) host data movement (CSR
reassembly, shifted NO copies, the CO merge). Part by part:

  * **similarity** — σ(u, v) depends only on N̄(u) and N̄(v), so an edit
    batch changes σ exactly for edges with a touched endpoint. The live
    plan's successor is derived block-patch-wise (``plan.apply``, work
    proportional to touched rows/classes, seeded into the plan cache for
    the post-edit graph) and the frontier routes through it exactly as in
    construction: (probe class, target class) kernels, power-of-two padded
    chunks → repeated update calls reuse one compiled function per class
    pair, and **only the affected degree classes re-run**; every other σ
    is carried over bit-for-bit.
  * **neighbor order (NO)** — rows whose content changed (touched vertices
    and their current neighbors) are re-sorted locally; every other row is
    copied with a position shift (its sorted content is unchanged, only
    its CSR offset moved).
  * **core order (CO)** — entries of unaffected rows keep their relative
    order in the (μ asc, θ desc, v asc) global sort, so CO repair is a
    *merge* of the kept entries with the re-sorted affected entries —
    O(m) movement, no global sort.

**Bit-identity with rebuild** is the maintained invariant (asserted by the
edit-script oracle in ``tests/test_incremental_index.py``): after any
update sequence the index equals ``build_index(from_edge_list(n, edges))``
array-for-array. Two properties make that possible:

  1. every sort key used during construction is *unique* (a NO slot is
     keyed by (row, -σ, ¬self, nbr); a CO slot by (μ, -θ, v)), so host
     ``np.lexsort`` and device ``jnp.lexsort`` agree exactly;
  2. σ bit patterns depend only on *local* quantities — the two endpoint
     rows, their power-of-two degree-class widths/tile counts, and the
     endpoint norms — all of which change exactly for touched endpoints.
     The degree-bucketed engine therefore needs **no global fallback**:
     the old dense-padded layout's "padded width changed → full σ
     recompute" escape hatch is gone, because a hub edit perturbs only its
     own degree class, never every vertex's kernel width.

Deletes are applied before inserts, so a delete+insert of the same edge in
one batch re-inserts it (with the new weight). Deleting an absent edge and
re-inserting an identical one are no-ops and do not grow the frontier.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.graph import CSRGraph, from_edge_list
from repro.core.index import ScanIndex
from repro.core import similarity as sim_mod


MAX_VERTEX_ID = 2 ** 31 - 1   # the packed (u, v) merge key is one int64


def _pack(u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Order-preserving (u, v) → int64 key. Ids must fit in 31 bits —
    enforced at :meth:`EdgeDelta.make` / ``from_edge_list`` (a wider id
    would silently collide keys and corrupt the CO merge)."""
    return (u.astype(np.int64) << 32) | v.astype(np.int64)


def _check_id_width(*arrays) -> None:
    """Reject vertex ids the packed int64 edit keys cannot represent."""
    for a in arrays:
        if len(a) and int(np.max(a)) > MAX_VERTEX_ID:
            raise ValueError(
                f"vertex id {int(np.max(a))} exceeds {MAX_VERTEX_ID} "
                "(2**31 - 1): ids must fit in 31 bits for the packed "
                "edit-merge keys")


@dataclasses.dataclass(frozen=True)
class EdgeDelta:
    """One canonical batch of undirected edge edits.

    Arrays hold canonical (u < v) endpoint pairs: ``del_*`` first, then
    ``ins_*`` with per-edge weights. Build via :meth:`make` (dedups,
    canonicalizes, drops self-loops; duplicate inserts keep the last
    weight).
    """

    ins_u: np.ndarray   # int64[K]
    ins_v: np.ndarray   # int64[K]
    ins_w: np.ndarray   # float32[K]
    del_u: np.ndarray   # int64[L]
    del_v: np.ndarray   # int64[L]

    @staticmethod
    def make(
        inserts: Optional[Sequence[Tuple[int, int]] | np.ndarray] = None,
        deletes: Optional[Sequence[Tuple[int, int]] | np.ndarray] = None,
        weights: Optional[Sequence[float] | np.ndarray] = None,
    ) -> "EdgeDelta":
        ins = np.asarray(inserts if inserts is not None else [],
                         dtype=np.int64).reshape(-1, 2)
        dels = np.asarray(deletes if deletes is not None else [],
                          dtype=np.int64).reshape(-1, 2)
        _check_id_width(ins.reshape(-1), dels.reshape(-1))
        if weights is None:
            w = np.ones(len(ins), dtype=np.float32)
        else:
            w = np.asarray(weights, dtype=np.float32)
            if len(w) != len(ins):
                raise ValueError("weights length must match inserts length")
        keep = ins[:, 0] != ins[:, 1]
        ins, w = ins[keep], w[keep]
        ilo = np.minimum(ins[:, 0], ins[:, 1])
        ihi = np.maximum(ins[:, 0], ins[:, 1])
        # duplicate inserts: LAST weight wins (unique-first on the reversal)
        _, first = np.unique(_pack(ilo, ihi)[::-1], return_index=True)
        sel = len(ilo) - 1 - first
        ilo, ihi, w = ilo[sel], ihi[sel], w[sel]

        dels = dels[dels[:, 0] != dels[:, 1]]
        dlo = np.minimum(dels[:, 0], dels[:, 1])
        dhi = np.maximum(dels[:, 0], dels[:, 1])
        _, first = np.unique(_pack(dlo, dhi), return_index=True)
        dlo, dhi = dlo[first], dhi[first]
        return EdgeDelta(ins_u=ilo, ins_v=ihi, ins_w=w.astype(np.float32),
                         del_u=dlo, del_v=dhi)

    def __len__(self) -> int:
        return len(self.ins_u) + len(self.del_u)


def random_delta(g: CSRGraph, k: int, rng) -> EdgeDelta:
    """K synthetic edits against ``g``: ~K/2 deletes of existing edges,
    ~K/2 random inserts (shared by the bench and the CLI edit stream)."""
    eu, ev = np.asarray(g.edge_u), np.asarray(g.nbrs)
    canon = np.flatnonzero(eu < ev)
    n_del = min(k // 2, len(canon))
    pick = (rng.choice(canon, size=n_del, replace=False)
            if n_del else np.zeros(0, np.int64))
    dels = np.stack([eu[pick], ev[pick]], axis=1)
    ins = rng.integers(0, g.n, size=(k - n_del, 2))
    w = rng.uniform(0.1, 1.0, size=len(ins)).astype(np.float32)
    return EdgeDelta.make(inserts=ins, weights=w, deletes=dels)


@dataclasses.dataclass(frozen=True)
class UpdateInfo:
    """What one ``apply_delta`` actually did (observability + bench)."""

    n_inserted: int        # effective inserts (new edge or weight change)
    n_deleted: int         # effective deletes (edge existed)
    n_touched: int         # endpoints whose neighborhood changed
    n_frontier: int        # half-edges whose σ was recomputed
    n_affected_rows: int   # NO rows re-sorted (touched ∪ their neighbors)
    n_sim_groups: int      # degree-class kernel groups the frontier ran
    n_plan_rows: int = 0   # block tile rows SimilarityPlan.apply rewrote
    n_plan_classes: int = 0  # class blocks not reused (patched/remapped/built)
    # Vertices whose *local* query result could differ from the
    # predecessor index: the affected rows (touched ∪ frontier-edge
    # endpoints — every vertex whose core bit, row order, or incident σ
    # could have changed) closed under two adjacency hops of the new
    # graph. Two hops because a border can re-attach into a cluster it
    # never touched: an edit flips a core bit at z, z's neighbor b falls
    # through to its next-best core c, and b joins c's cluster — c is
    # two hops from z. Any seed outside this set, whose members avoid
    # it, provably keeps a bit-identical answer (the serve layer's
    # seed-cache invalidation rule).
    frontier_vertices: Optional[np.ndarray] = None  # int ids, sorted

    def stale_mask(self, n: int) -> np.ndarray:
        """bool[n] over :attr:`frontier_vertices` (empty → all-False)."""
        mask = np.zeros(n, dtype=bool)
        if self.frontier_vertices is not None:
            mask[self.frontier_vertices] = True
        return mask


def _edit_edge_set(g: CSRGraph, delta: EdgeDelta):
    """Apply the batch to the canonical edge set (host side).

    Returns (new_lo, new_hi, new_w, touched_vertex_ids, n_ins, n_del) —
    ``touched`` holds only endpoints of *effective* edits.
    """
    eu = np.asarray(g.edge_u)
    ev = np.asarray(g.nbrs)
    w = np.asarray(g.wgts)
    mask = eu < ev
    lo, hi, wc = eu[mask], ev[mask], w[mask]
    keys = _pack(lo, hi)                      # ascending (CSR lex order)

    # -- deletes first --
    dkeys = _pack(delta.del_u.astype(np.int64), delta.del_v.astype(np.int64))
    pos = np.searchsorted(keys, dkeys)
    dhit = (pos < len(keys)) & (keys[np.minimum(pos, max(len(keys) - 1, 0))]
                                == dkeys) if len(keys) else np.zeros(
                                    len(dkeys), bool)
    keep = np.ones(len(keys), dtype=bool)
    keep[pos[dhit]] = False

    # -- inserts --
    ikeys = _pack(delta.ins_u.astype(np.int64), delta.ins_v.astype(np.int64))
    ipos = np.searchsorted(keys, ikeys)
    ipresent = (ipos < len(keys)) & (
        keys[np.minimum(ipos, max(len(keys) - 1, 0))] == ikeys
    ) if len(keys) else np.zeros(len(ikeys), bool)
    ipresent &= keep[np.minimum(ipos, max(len(keys) - 1, 0))] if len(keys) \
        else False
    same_w = np.zeros(len(ikeys), dtype=bool)
    if len(keys):
        same_w[ipresent] = (
            wc[ipos[ipresent]].view(np.uint32)
            == delta.ins_w[ipresent].view(np.uint32))
    effective_ins = ~(ipresent & same_w)      # new edge OR weight change
    # rows being overwritten by an insert drop out of the kept set
    keep[ipos[ipresent]] = False

    new_lo = np.concatenate([lo[keep], delta.ins_u])
    new_hi = np.concatenate([hi[keep], delta.ins_v])
    new_w = np.concatenate([wc[keep], delta.ins_w]).astype(np.float32)
    order = np.argsort(_pack(new_lo, new_hi), kind="stable")
    new_lo, new_hi, new_w = new_lo[order], new_hi[order], new_w[order]

    touched = np.unique(np.concatenate([
        delta.del_u[dhit], delta.del_v[dhit],
        delta.ins_u[effective_ins], delta.ins_v[effective_ins]]))
    return (new_lo, new_hi, new_w, touched,
            int(effective_ins.sum()), int(dhit.sum()))


def _repair_no(index: ScanIndex, g2: CSRGraph, sims2: np.ndarray,
               aff_mask: np.ndarray):
    """New NO arrays: shifted copy for unaffected rows, local sort for
    affected rows. Returns (offsets_c_new, no_nbrs, no_sims, no_self,
    row_of_new_slot)."""
    n = g2.n
    off2 = np.asarray(g2.offsets)
    eu2 = np.asarray(g2.edge_u)
    ev2 = np.asarray(g2.nbrs)
    cdeg_old = np.asarray(index.cdeg)
    cdeg_new = np.diff(off2) + 1
    offc_old = np.asarray(index.offsets_c)
    offc_new = (off2 + np.arange(n + 1, dtype=np.int32)).astype(np.int32)
    m2c_new = g2.m2 + n

    row_old = np.repeat(np.arange(n), cdeg_old)
    row_new = np.repeat(np.arange(n), cdeg_new)

    no_nbrs = np.empty(m2c_new, np.int32)
    no_sims = np.empty(m2c_new, np.float32)
    no_self = np.empty(m2c_new, bool)

    unaff = ~aff_mask[row_old]
    if unaff.any():
        shift = offc_new[:n].astype(np.int64) - offc_old[:n]
        src = np.flatnonzero(unaff)
        dst = src + shift[row_old[src]]
        no_nbrs[dst] = np.asarray(index.no_nbrs)[src]
        no_sims[dst] = np.asarray(index.no_sims)[src]
        no_self[dst] = np.asarray(index.no_self)[src]

    aff_rows = np.flatnonzero(aff_mask)
    if len(aff_rows):
        aff_edge = aff_mask[eu2]
        rows_a = np.concatenate([aff_rows, eu2[aff_edge]])
        nbrs_a = np.concatenate([aff_rows, ev2[aff_edge]])
        sims_a = np.concatenate([
            np.ones(len(aff_rows), np.float32), sims2[aff_edge]])
        notself_a = np.concatenate([
            np.zeros(len(aff_rows), np.int32),
            np.ones(int(aff_edge.sum()), np.int32)])
        # same (unique) key order as _build_orders' global NO sort
        perm = np.lexsort((nbrs_a, notself_a, -sims_a, rows_a))
        dst = np.flatnonzero(aff_mask[row_new])
        no_nbrs[dst] = nbrs_a[perm].astype(np.int32)
        no_sims[dst] = sims_a[perm]
        no_self[dst] = notself_a[perm] == 0
    return offc_new, no_nbrs, no_sims, no_self, row_new


def _merge_co(kept_v, kept_t, kept_mu, new_v, new_t, new_mu, n, max_cdeg):
    """Merge two (μ asc, θ desc, v asc)-sorted CO entry runs.

    Keys are packed into uint64 when they fit (μ | sortable(-θ) | v) so the
    merge is two searchsorteds; otherwise falls back to one stable lexsort
    over the concatenation (still exact — keys are unique)."""
    total = len(kept_v) + len(new_v)
    co_v = np.empty(total, np.int32)
    co_t = np.empty(total, np.float32)
    vbits = max(int(n - 1).bit_length(), 1) if n > 1 else 1
    mubits = max(int(max_cdeg).bit_length(), 1)
    if mubits + 32 + vbits <= 64:
        def key(mu, t, v):
            tdesc = np.uint64(0xFFFFFFFF) - t.astype(np.float32).view(
                np.uint32).astype(np.uint64)
            return ((mu.astype(np.uint64) << np.uint64(32 + vbits))
                    | (tdesc << np.uint64(vbits)) | v.astype(np.uint64))
        kk = key(kept_mu, kept_t, kept_v)
        nk = key(new_mu, new_t, new_v)
        pos_k = np.arange(len(kk)) + np.searchsorted(nk, kk)
        pos_n = np.arange(len(nk)) + np.searchsorted(kk, nk)
        co_v[pos_k], co_t[pos_k] = kept_v, kept_t
        co_v[pos_n], co_t[pos_n] = new_v, new_t
    else:  # pragma: no cover - graphs beyond the packable id range
        mu = np.concatenate([kept_mu, new_mu])
        t = np.concatenate([kept_t, new_t]).astype(np.float32)
        v = np.concatenate([kept_v, new_v])
        perm = np.lexsort((v, -t, mu))
        co_v, co_t = v[perm].astype(np.int32), t[perm]
    return co_v, co_t


def apply_delta(
    index: ScanIndex,
    g: CSRGraph,
    delta: EdgeDelta,
    measure: str = "cosine",
) -> Tuple[ScanIndex, CSRGraph, UpdateInfo]:
    """Maintain (index, graph) under one edit batch.

    Returns ``(new_index, new_graph, info)``; the inputs are untouched
    (both are frozen dataclasses), so callers can hot-swap atomically.
    The result is bit-identical to ``build_index(new_graph, measure)``.
    """
    n = g.n
    if len(delta.ins_u) and (int(delta.ins_v.max()) >= n
                             or int(delta.ins_u.min()) < 0):
        raise ValueError("insert endpoint out of range")
    if len(delta.del_u) and (int(delta.del_v.max()) >= n
                             or int(delta.del_u.min()) < 0):
        raise ValueError("delete endpoint out of range")

    new_lo, new_hi, new_w, touched, n_ins, n_del = _edit_edge_set(g, delta)
    g2 = from_edge_list(n, np.stack([new_lo, new_hi], axis=1)
                        if len(new_lo) else np.zeros((0, 2), np.int64),
                        new_w)
    eu2 = np.asarray(g2.edge_u)
    ev2 = np.asarray(g2.nbrs)

    touched_mask = np.zeros(n, dtype=bool)
    touched_mask[touched] = True
    frontier = (touched_mask[eu2] | touched_mask[ev2]) if g2.m2 else \
        np.zeros(0, dtype=bool)

    # ---- bucketed plan: patch the live blocks, never rebuild O(m) ----
    # The predecessor plan (cached per live graph; built once if this is
    # the first delta against a cold graph) is maintained block-patch-wise
    # and seeded into the cache for g2 — construction work per batch is
    # proportional to touched rows/classes.
    plan2 = sim_mod.adopt_plan(
        g2, sim_mod.plan_for(g).apply(g2, touched))
    pstats = plan2.last_apply

    # ---- σ: carry unchanged edges, recompute the frontier ----
    # Per-edge kernel widths are local degree classes, so an edit can never
    # invalidate a carried σ bit pattern: only the frontier's own degree
    # classes re-run, whatever the batch does to the degree distribution.
    sims2 = np.empty(g2.m2, np.float32)
    n_sim_groups = 0
    if (~frontier).any():
        hk_old = _pack(np.asarray(g.edge_u), np.asarray(g.nbrs))
        hk_new = _pack(eu2[~frontier], ev2[~frontier])
        sims2[~frontier] = np.asarray(index.edge_sims)[
            np.searchsorted(hk_old, hk_new)]
    n_frontier = int(frontier.sum())
    if n_frontier:
        fr = plan2.edge_sims(
            eu2[frontier], ev2[frontier],
            np.asarray(g2.wgts)[frontier], measure)
        sims2[frontier] = np.clip(np.asarray(fr), 0.0, 1.0)
        n_sim_groups = plan2.last_groups

    # ---- NO repair ----
    aff_mask = touched_mask.copy()
    if g2.m2:
        aff_mask[eu2[frontier]] = True
    offc_new, no_nbrs, no_sims, no_self, row_new = _repair_no(
        index, g2, sims2, aff_mask)

    # ---- CO repair (merge) ----
    m2c_new = g2.m2 + n
    mu_slot = (np.arange(m2c_new, dtype=np.int64)
               - offc_new[row_new].astype(np.int64) + 1)
    co_old_v = np.asarray(index.co_vertex)
    co_old_t = np.asarray(index.co_theta)
    co_off_old = np.asarray(index.co_offsets)
    co_old_mu = (np.searchsorted(
        co_off_old, np.arange(len(co_old_v)), side="right") - 1) \
        if len(co_old_v) else np.zeros(0, np.int64)
    kept = ~aff_mask[co_old_v] if len(co_old_v) else np.zeros(0, bool)

    aff_co = aff_mask[row_new] & (mu_slot >= 2)
    av = row_new[aff_co]
    at = no_sims[aff_co]
    amu = mu_slot[aff_co]
    perm = np.lexsort((av, -at, amu))
    av, at, amu = av[perm], at[perm], amu[perm]

    cdeg_new = (np.diff(np.asarray(g2.offsets)) + 1).astype(np.int32)
    max_cdeg = int(cdeg_new.max()) if n else 1
    co_v, co_t = _merge_co(co_old_v[kept], co_old_t[kept], co_old_mu[kept],
                           av, at, amu, n, max_cdeg)

    counts = np.bincount(
        np.concatenate([co_old_mu[kept], amu]).astype(np.int64),
        minlength=max_cdeg + 1)
    co_offsets = np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(counts)]).astype(np.int32)

    new_index = ScanIndex(
        offsets_c=jnp.asarray(offc_new),
        no_nbrs=jnp.asarray(no_nbrs),
        no_sims=jnp.asarray(no_sims),
        no_self=jnp.asarray(no_self),
        co_offsets=jnp.asarray(co_offsets),
        co_vertex=jnp.asarray(co_v),
        co_theta=jnp.asarray(co_t),
        cdeg=jnp.asarray(cdeg_new),
        edge_sims=jnp.asarray(sims2),
        n=n,
        m2c=m2c_new,
        max_cdeg=max_cdeg,
    )
    # seed-cache invalidation set: affected rows closed under two
    # adjacency hops of the new graph (see UpdateInfo.frontier_vertices
    # for why two) — O(m) boolean gathers, host-side
    stale = aff_mask.copy()
    for _ in range(2):
        ext = np.zeros(n, dtype=bool)
        if g2.m2:
            ext[ev2[stale[eu2]]] = True
        stale |= ext

    info = UpdateInfo(
        n_inserted=n_ins, n_deleted=n_del, n_touched=len(touched),
        n_frontier=n_frontier, n_affected_rows=int(aff_mask.sum()),
        n_sim_groups=n_sim_groups,
        n_plan_rows=pstats["rows_written"],
        n_plan_classes=(pstats["patched"] + pstats["remapped"]
                       + pstats["built"]),
        frontier_vertices=np.flatnonzero(stale))
    return new_index, g2, info
