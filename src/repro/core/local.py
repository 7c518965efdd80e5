"""Seed-set (local) clustering queries (ROADMAP "Local queries" item).

Per-user traffic asks "what is *my* community at (μ, ε)?" — answering it
with the global ``query()`` materializes all n labels to read one row.
Following the local-graph-clustering framing (Shun et al., PAPERS.md),
the GS*-Index already supports output-sensitive resolution: core
membership is one θ-slot probe per vertex (v is a (μ, ε)-core iff the
μ-th entry of NO[v], i.e. ``core_threshold(v, μ)``, is ≥ ε), and the seed's
cluster is the connected component of its attachment core in the
ε-similar core–core graph, reachable by frontier expansion over NO row
prefixes. Work scales with the *output cluster*, not with n.

Shapes are serve-grade static: the expansion is a ``lax.while_loop``
over a pow2-capacity frontier (``frontier_cap``), each iteration
gathering a fixed ``window`` of every frontier row's ε-prefix (NO rows
are σ-descending, so the prefix is contiguous) and folding new cores in
with one sort-based set union — so thousands of concurrent seed
requests vmap into a single compiled artifact per
(frontier_cap, window, border_cap) triple.

Spill-to-full-query fallback: anything that outgrows the caps — a
cluster with more cores than ``frontier_cap``, an ε-prefix longer than
``window`` (only when the entry just past the window is still ε-similar;
a short row is not a spill), more candidate borders than ``border_cap``,
or a border row whose attachment is undecided inside its window — sets
the lane's ``spilled`` flag, and the host wrapper re-answers exactly
those lanes through the full ``query_batch``, padded to a fixed
``fallback_batch`` lane count so the fallback reuses one artifact too.
Either way every answer is **bit-identical** to extracting the seed's
row from the full ``query()`` output — the serve-layer seed cache and
the oracle tests depend on this.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import CSRGraph
from repro.core.index import ScanIndex
from repro.core.query import query_batch

DEFAULT_FRONTIER_CAP = 128   # member/frontier slots per lane (pow2)
DEFAULT_WINDOW = 32          # NO-row ε-prefix entries gathered per vertex
DEFAULT_BORDER_CAP = 512     # candidate-border slots per lane (pow2)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SeedBatchResult:
    """Answers for a batch of (seed, μ, ε) lanes."""

    labels: jax.Array       # int32[B]   seed's cluster label; -1 unclustered
    is_core: jax.Array      # bool[B]    seed is a (μ, ε)-core
    member_mask: jax.Array  # bool[B, n] membership of the seed's cluster
    n_members: jax.Array    # int32[B]   popcount of each member_mask row
    spilled: jax.Array      # bool[B]    lane exceeded a static cap


@dataclasses.dataclass(frozen=True)
class SeedResult:
    """One seed's answer, host-side — the unit the serve cache stores."""

    seed: int
    label: int
    is_core: bool
    member_mask: np.ndarray  # bool[n]

    @property
    def n_members(self) -> int:
        return int(self.member_mask.sum())

    @property
    def members(self) -> np.ndarray:
        return np.flatnonzero(self.member_mask)

    @staticmethod
    def from_batch_row(res: SeedBatchResult, i: int, seed: int
                       ) -> "SeedResult":
        # copy: a row view would pin the whole [B, n] batch array in the
        # cache for as long as the entry lives
        return SeedResult(seed=int(seed), label=int(res.labels[i]),
                          is_core=bool(res.is_core[i]),
                          member_mask=np.array(res.member_mask[i]))


def _is_core(index: ScanIndex, v, mu, eps):
    """(μ, ε)-core test for vertex ids ``v`` (any shape; out-of-range ids
    test non-core). One θ-slot probe per vertex: NO rows are σ-descending
    with the self slot pinned first, so the μ-th row entry *is* the core
    threshold θ(v, μ). Bit-identical to ``get_cores(index, mu, eps)[v]``,
    which evaluates the same test for every vertex."""
    vc = jnp.clip(v, 0, index.n - 1)
    slot = index.offsets_c[vc].astype(jnp.int32) + (mu - 1)
    theta = index.no_sims[jnp.clip(slot, 0, index.m2c - 1)]
    ok = (v >= 0) & (v < index.n) & (mu >= 2) & (index.cdeg[vc] >= mu)
    return ok & (theta >= eps)


def _row_windows(index: ScanIndex, vs, mu, eps, window: int):
    """ε-prefix windows: positions 1..window of each NO row (position 0
    is always the self slot — σ(v,v)=1 sorts first). Returns
    (nbrs int32[K, W], keep bool[K, W], spill bool[K]); ``spill`` marks
    rows whose ε-prefix continues past the window — the only case the
    gather under-reports."""
    n = index.n
    vc = jnp.clip(vs, 0, n - 1)
    base = index.offsets_c[vc].astype(jnp.int32)
    width = index.cdeg[vc]
    valid = (vs >= 0) & (vs < n)
    pos = jnp.arange(1, window + 1, dtype=jnp.int32)
    slot = jnp.clip(base[:, None] + pos[None, :], 0, index.m2c - 1)
    nbrs = index.no_nbrs[slot]
    keep = (valid[:, None] & (pos[None, :] < width[:, None])
            & (index.no_sims[slot] >= eps))
    over = jnp.clip(base + jnp.int32(window + 1), 0, index.m2c - 1)
    spill = valid & (width > window + 1) & (index.no_sims[over] >= eps)
    return nbrs, keep, spill


def _compact(ids, mask, cap: int, sentinel):
    """Scatter the ``mask``-selected (ascending) ids into a fixed ``cap``
    slots, sentinel-padded; selections past ``cap`` drop (caller flags
    the overflow as a spill)."""
    pos = jnp.cumsum(mask) - 1
    idx = jnp.where(mask & (pos < cap), pos, cap)
    return (jnp.full((cap,), sentinel, jnp.int32)
            .at[idx].set(jnp.where(mask, ids, sentinel), mode="drop"))


def _seed_lane(index: ScanIndex, seed, mu, eps,
               fcap: int, window: int, bcap: int):
    """One (seed, μ, ε) lane; returns (label, is_core, member_mask,
    n_members, spilled). Designed as a fixed point once converged, so
    vmap's run-until-all-done semantics for ``while_loop`` are safe."""
    n = index.n
    sentinel = jnp.int32(n)
    seed_core = _is_core(index, seed, mu, eps)

    # Non-core seed: its cluster (if any) is the component of its
    # attachment core — the *first* core in the row's ε-prefix. The NO
    # tie order (σ desc, then neighbor id asc) makes "first" exactly the
    # full query's deterministic border rule: max σ, ties to the lower
    # core id.
    nb0, ok0, sp0 = _row_windows(index, seed[None], mu, eps, window)
    cand0 = ok0[0] & _is_core(index, nb0[0], mu, eps)
    has_attach = jnp.any(cand0)
    attach = jnp.where(has_attach, nb0[0, jnp.argmax(cand0)], sentinel)
    # no core inside the window but the ε-prefix continues: the true
    # attachment may lie beyond the window — undecidable without spill
    spill0 = (~seed_core) & (~has_attach) & sp0[0]

    start = jnp.where(seed_core, jnp.asarray(seed, jnp.int32), attach)
    members0 = jnp.full((fcap,), sentinel, jnp.int32).at[0].set(start)

    def cond(state):
        _, _, spill, n_new, it = state
        # each growing iteration adds ≥ 1 member, so fcap + 2 bounds the
        # loop even without the n_new test (belt and braces)
        return (n_new > 0) & (~spill) & (it < fcap + 2)

    def body(state):
        members, frontier, spill, _, it = state
        nb, keep, sp = _row_windows(index, frontier, mu, eps, window)
        keep = keep & _is_core(index, nb, mu, eps)
        spill = spill | jnp.any(sp)
        cand = jnp.where(keep, nb, sentinel).reshape(-1)
        # sort-based set union on packed (id, origin) keys: a member of
        # equal id sorts before a candidate, so a candidate surviving
        # first-occurrence filtering is genuinely new. int32 pack needs
        # 2·(n+1) ≤ 2³¹ — the wrapper enforces n < 2³⁰.
        key = jnp.sort(jnp.concatenate([members * 2, cand * 2 + 1]))
        ids = key >> 1
        fresh = (key & 1) == 1
        first = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
        first = first & (ids < n)
        new = first & fresh
        n_new = jnp.sum(new).astype(jnp.int32)
        spill = spill | (jnp.sum(first) > fcap)
        return (_compact(ids, first, fcap, sentinel),
                _compact(ids, new, fcap, sentinel),
                spill, n_new, it + 1)

    n_new0 = jnp.where(start < n, 1, 0).astype(jnp.int32)
    members, _, spill, _, _ = jax.lax.while_loop(
        cond, body, (members0, members0, spill0, n_new0, jnp.int32(0)))
    label = jnp.where(members[0] < n, members[0], jnp.int32(-1))

    # ---- border pass: non-core ε-neighbors of the member cores ----
    nb, keep, sp = _row_windows(index, members, mu, eps, window)
    spill = spill | jnp.any(sp)
    bc = jnp.where(keep & ~_is_core(index, nb, mu, eps), nb, sentinel)
    bs = jnp.sort(bc.reshape(-1))
    bfirst = jnp.concatenate([jnp.ones((1,), bool), bs[1:] != bs[:-1]])
    bfirst = bfirst & (bs < n)
    spill = spill | (jnp.sum(bfirst) > bcap)
    borders = _compact(bs, bfirst, bcap, sentinel)
    # each candidate attaches to the first core of its *own* ε-prefix;
    # it joins this cluster only if that core is one of the members
    bnb, bok, bsp = _row_windows(index, borders, mu, eps, window)
    bcore = bok & _is_core(index, bnb, mu, eps)
    bhas = jnp.any(bcore, axis=1)
    battach = jnp.where(
        bhas, bnb[jnp.arange(bcap), jnp.argmax(bcore, axis=1)], sentinel)
    # a border row with no core inside its window whose ε-prefix
    # continues is undecided about its attachment
    spill = spill | jnp.any((~bhas) & bsp)
    pos = jnp.searchsorted(members, battach)  # members sorted ascending
    inside = (members[jnp.clip(pos, 0, fcap - 1)] == battach) & (battach < n)
    accepted = jnp.where(bhas & inside, borders, sentinel)

    mask = jnp.zeros((n + 1,), bool)          # slot n absorbs sentinels
    mask = mask.at[members].set(True).at[accepted].set(True)[:n]
    return (label, seed_core, mask,
            jnp.sum(mask).astype(jnp.int32), spill)


@functools.partial(
    jax.jit, static_argnames=("frontier_cap", "window", "border_cap"))
def query_seeds_device(index: ScanIndex, g: CSRGraph, seeds, mus, epss, *,
                       frontier_cap: int = DEFAULT_FRONTIER_CAP,
                       window: int = DEFAULT_WINDOW,
                       border_cap: int = DEFAULT_BORDER_CAP
                       ) -> SeedBatchResult:
    """Device half of :func:`query_seeds`: one fixed-shape vmapped call,
    no host fallback — spilled lanes come back flagged, not resolved.
    ``g`` rides along for signature parity with the full query path (the
    kernel reads only the index arrays)."""
    del g
    seeds = jnp.atleast_1d(jnp.asarray(seeds, jnp.int32))
    mus = jnp.broadcast_to(jnp.asarray(mus, jnp.int32), seeds.shape)
    epss = jnp.broadcast_to(jnp.asarray(epss, jnp.float32), seeds.shape)
    label, core, mask, n_mem, spill = jax.vmap(
        lambda s, m, e: _seed_lane(index, s, m, e,
                                   frontier_cap, window, border_cap)
    )(seeds, mus, epss)
    return SeedBatchResult(labels=label, is_core=core, member_mask=mask,
                           n_members=n_mem, spilled=spill)


def _pow2(k: int) -> int:
    return 1 << max(k - 1, 0).bit_length()


def query_seeds(index: ScanIndex, g: CSRGraph, seeds, mu, eps, *,
                frontier_cap: int = DEFAULT_FRONTIER_CAP,
                window: int = DEFAULT_WINDOW,
                border_cap: int = DEFAULT_BORDER_CAP,
                fallback_batch: int | None = None) -> SeedBatchResult:
    """Per-seed cluster queries; host-side numpy results.

    ``mu`` / ``eps`` may be scalars (applied to every seed) or per-seed
    arrays. Lanes that exceed a static cap are transparently re-answered
    through the full ``query_batch``, padded to ``fallback_batch`` lanes
    (default: the spill count rounded up to a pow2) so repeated spills
    share one compiled artifact. Every row — expanded or fallen back —
    is bit-identical to extracting the seed's row from the full
    ``query()`` output: label, core flag, and the membership mask
    ``labels == labels[seed]`` (all-False when the seed is unclustered).
    """
    for name, cap in (("frontier_cap", frontier_cap), ("window", window),
                      ("border_cap", border_cap)):
        if cap < 1 or cap & (cap - 1):
            raise ValueError(f"{name} must be a power of two, got {cap}")
    if index.n >= 1 << 30:
        raise ValueError("seed kernel packs (id, origin) into int32 "
                         "keys: n must be < 2**30")
    seeds = np.atleast_1d(np.asarray(seeds, np.int32))
    if seeds.size == 0:
        return SeedBatchResult(
            labels=np.zeros(0, np.int32), is_core=np.zeros(0, bool),
            member_mask=np.zeros((0, index.n), bool),
            n_members=np.zeros(0, np.int32), spilled=np.zeros(0, bool))
    if int(seeds.min()) < 0 or int(seeds.max()) >= index.n:
        raise ValueError("seed vertex id out of range")
    b = seeds.shape[0]
    mus = np.broadcast_to(np.asarray(mu, np.int32), (b,))
    epss = np.broadcast_to(np.asarray(eps, np.float32), (b,))
    res = query_seeds_device(index, g, seeds, mus, epss,
                             frontier_cap=frontier_cap, window=window,
                             border_cap=border_cap)
    labels = np.asarray(res.labels).copy()
    is_core = np.asarray(res.is_core).copy()
    mask = np.asarray(res.member_mask).copy()
    n_mem = np.asarray(res.n_members).copy()
    spilled = np.asarray(res.spilled).copy()
    rows = np.flatnonzero(spilled)
    if len(rows):
        pad = int(fallback_batch) if fallback_batch else _pow2(len(rows))
        for lo in range(0, len(rows), pad):
            chunk = rows[lo:lo + pad]
            cm = np.full(pad, mus[chunk[0]], np.int32)
            ce = np.full(pad, epss[chunk[0]], np.float32)
            cm[:len(chunk)] = mus[chunk]
            ce[:len(chunk)] = epss[chunk]
            full = query_batch(index, g, cm, ce)
            flab = np.asarray(full.labels)
            fcore = np.asarray(full.is_core)
            for i, r in enumerate(chunk):
                s = int(seeds[r])
                lab = int(flab[i, s])
                labels[r] = lab
                is_core[r] = bool(fcore[i, s])
                row = (flab[i] == lab) if lab >= 0 \
                    else np.zeros(index.n, bool)
                mask[r] = row
                n_mem[r] = int(row.sum())
    return SeedBatchResult(labels=labels, is_core=is_core,
                           member_mask=mask, n_members=n_mem,
                           spilled=spilled)
