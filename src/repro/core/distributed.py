"""Distributed SCAN index construction (beyond-paper, pod-scale posture).

The paper targets one shared-memory node. At pod scale the natural
decomposition keeps the similarity pass *edge-parallel*: half-edges are
sharded across the ``data`` axis of the mesh with ``shard_map``; the
degree-bucketed neighbor blocks (or, for dense graphs, the packed LSH
sketches — 32× smaller) are replicated/all-gathered. Bucketing shrinks the
replicated operand from the old O(n·Δ) dense padded matrix to O(m + n)
class blocks — on skewed graphs that is the difference between replicating
gigabytes and megabytes per device. The LSH sketches double as a further
*communication compressor*: a k-bit sketch per vertex replaces the full
neighbor row, which is exactly the paper's "LSH wins on dense graphs"
insight re-applied to the network instead of the cache.

The global sorts for NO/CO lower to XLA's distributed sort under pjit.
"""
from __future__ import annotations

import functools
import hashlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.connectivity import RowLayout, row_steps
from repro.core.graph import CSRGraph
from repro.core import lsh as lsh_mod


@functools.partial(
    jax.jit, static_argnames=("sp", "st", "measure", "mesh", "axis",
                              "kernel"))
def _sharded_bucket_group(p0, pt, t0, tt, eu, ev, ew,
                          p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg,
                          *, sp, st, measure, mesh, axis, kernel=None):
    """One group chunk, edges sharded. ``kernel`` = None runs the jnp
    engine (``ref`` lane); (be, bt, interpret) runs the Pallas lane."""
    from repro.core.similarity import (_bucket_sims_core,
                                       _bucket_sims_pallas_core)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis),) * 7 + (P(None, None),) * 4 + (P(None), P(None)),
        out_specs=P(axis),
        check_vma=False,
    )
    def _shard(p0, pt, t0, tt, eu, ev, ew, p_n, p_w, t_n, t_w, nrm, cd):
        if kernel is None:
            return _bucket_sims_core(p0, pt, t0, tt, eu, ev, ew,
                                     p_n, p_w, t_n, t_w, nrm, cd,
                                     sp, st, measure)
        return _bucket_sims_pallas_core(p0, pt, t0, tt, eu, ev, ew,
                                        p_n, p_w, t_n, t_w, nrm, cd,
                                        sp, st, measure, *kernel)

    return _shard(p0, pt, t0, tt, eu, ev, ew,
                  p_nbr, p_wgt, t_nbr, t_wgt, norms, cdeg)


def sharded_edge_similarities(
    g: CSRGraph,
    plan=None,
    mesh: Mesh | None = None,
    axis: str = "data",
    measure: str = "cosine",
    policy=None,
) -> jax.Array:
    """σ per half-edge with the edge axis sharded over ``axis``.

    Degree-bucketed twin of :func:`repro.core.similarity.compute_similarities`:
    edges are routed host-side to (probe class, target class) groups, each
    group is padded to a multiple of the axis size and runs as one
    ``shard_map`` over its sharded edge chunk, with the two class blocks
    (O(m + n) total, not the old O(n·Δ) padded matrix) replicated.

    Each group resolves its lane through the execution policy exactly as
    :meth:`SimilarityPlan.edge_sims` does (``hub_tile`` for a plan built
    here; the shard body runs the jnp engine on the ``ref`` lane and the
    Pallas probe kernel on the others), and the lanes' bit-identity
    contract keeps sharded σ equal to the single-host path.
    """
    from repro.backend.policy import LANE_COMPILED, LANE_REF, default_policy
    from repro.core import similarity as sim_mod

    pol = policy if policy is not None else default_policy()
    if plan is None:
        plan = sim_mod.plan_for(g, hub_tile=pol.profile.hub_tile)
    if mesh is None:
        mesh = query_mesh(axis=axis)
    k = mesh.shape[axis]
    eu = np.asarray(g.edge_u, np.int64)
    ev = np.asarray(g.nbrs, np.int64)
    ew = np.asarray(g.wgts, np.float32)
    if g.m2 == 0:
        return jnp.zeros((0,), jnp.float32)

    pu, pv, keys = plan.route(eu, ev)
    order = np.argsort(keys, kind="stable")
    bounds = np.flatnonzero(np.diff(keys[order])) + 1
    out = np.empty(g.m2, np.float32)
    for idx in np.split(order, bounds):
        cp = int(plan.vclass[pu[idx[0]]])
        ct = int(plan.vclass[pv[idx[0]]])
        sp = sim_mod._pow2ceil(int(plan.vtiles[pu[idx[0]]]))
        st = sim_mod._pow2ceil(int(plan.vtiles[pv[idx[0]]]))
        # same transient-working-set bound as the local engine, rounded up
        # to a k-multiple pow2-bucketed chunk so hub groups stream instead
        # of gathering one unbounded row matrix per device, and so repeated
        # graphs hit the same compiled shard_map shapes
        pe = sp * plan.widths[cp]
        te = st * plan.widths[ct]
        lane = pol.lane("bucket_probe")
        pol.note("bucket_probe", lane)
        cap = max(sim_mod.CHUNK_ELEMS // max(pe + te, 1), 1)
        cap = 1 << (cap.bit_length() - 1)
        kernel = None
        if lane != LANE_REF:
            if lane == LANE_COMPILED:
                # the kernel lays each shard's edges on the 128 lanes
                cap = max(cap, sim_mod.KERNEL_LANE_EDGES * k)
            else:   # interpret: bound the unrolled grid, as edge_sims does
                cap = min(cap, sim_mod._pow2ceil(
                    pol.profile.probe_interpret_chunk) * k)
        csize = -(-min(sim_mod._pow2_bucket(len(idx)), max(cap, 1)) // k) * k
        if lane != LANE_REF:
            kernel = (min(pol.profile.probe_be, csize // k),
                      pol.profile.probe_bt, pol.interpret(lane))
        sent_p = plan.nbr_blocks[cp].shape[0] - 1
        sent_t = plan.nbr_blocks[ct].shape[0] - 1
        for s in range(0, len(idx), csize):
            sub = idx[s: s + csize]
            pad = csize - len(sub)
            res = _sharded_bucket_group(
                jnp.asarray(sim_mod._pad1(plan.vrow[pu[sub]], pad, sent_p)),
                jnp.asarray(sim_mod._pad1(plan.vtiles[pu[sub]], pad, 0)),
                jnp.asarray(sim_mod._pad1(plan.vrow[pv[sub]], pad, sent_t)),
                jnp.asarray(sim_mod._pad1(plan.vtiles[pv[sub]], pad, 0)),
                jnp.asarray(sim_mod._pad1(eu[sub].astype(np.int32), pad, 0)),
                jnp.asarray(sim_mod._pad1(ev[sub].astype(np.int32), pad, 0)),
                jnp.asarray(sim_mod._pad1(ew[sub], pad, 0.0)),
                plan.nbr_blocks[cp], plan.wgt_blocks[cp],
                plan.nbr_blocks[ct], plan.wgt_blocks[ct],
                plan.norms, plan.cdeg,
                sp=sp, st=st, measure=measure, mesh=mesh, axis=axis,
                kernel=kernel)
            out[sub] = np.asarray(res)[: len(sub)]
    return jnp.asarray(out)


def sharded_simhash_edge_similarities(
    g: CSRGraph,
    sketches: jax.Array,
    samples: int,
    mesh: Mesh,
    axis: str = "data",
) -> jax.Array:
    """LSH comparison pass, edges sharded, sketches replicated (k bits/vertex)."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(None, None)),
        out_specs=P(axis),
        check_vma=False,
    )
    def _shard(eu, ev, sk):
        return lsh_mod.simhash_edge_similarity(sk, eu, ev, samples)

    return _shard(g.edge_u, g.nbrs, sketches)


# ---------------------------------------------------------------------------
# Sharded clustering queries (giant-graph serving path)
# ---------------------------------------------------------------------------
# The single-device ``core.query`` holds every O(m) array — half-edges,
# similarities, the NO slot arrays — on one device. For giant graphs the
# edge axis is the memory that runs out (GPUSCAN++'s observation), so the
# sharded query path partitions *every* edge-sized array over the mesh
# ``data`` axis and keeps only O(n) label/working vectors replicated:
#
#   * core extraction      — θ(v, μ) is one NO slot per vertex: the shard
#     holding it reads it, one pmax merges (no scatter).
#   * ε-similar filtering  — purely shard-local (each shard owns its edges).
#   * connectivity         — all-reduced label propagation
#     (:func:`repro.core.connectivity.connected_components`): a local
#     min over each row's slots in this shard, pmin-merge, pointer-jump on
#     the replicated labels.
#   * border attachment    — local row max/min + pmax/pmin merges.
#
# Every merge is an associative min/max, so each round reproduces the
# single-device reduction exactly → results are bit-identical to
# ``core.query_batch`` (asserted in tests/test_distributed_query.py).


def force_host_devices(k: int) -> None:
    """Ask XLA for ``k`` host-platform devices (CLI/bench/demo helper).

    Appends ``--xla_force_host_platform_device_count=k`` to ``XLA_FLAGS``
    unless a count is already forced. Must run before jax's backend
    initializes (the flag is read exactly once, at first device use) —
    importing jax is fine, touching devices is not.
    """
    if k <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={k}").strip()


def query_mesh(n_shards: int | None = None, axis: str = "data") -> Mesh:
    """1-D device mesh for sharded queries (defaults to every device)."""
    devs = jax.devices()
    if n_shards is None:
        n_shards = len(devs)
    if n_shards > len(devs):
        raise ValueError(
            f"requested {n_shards} shards but only {len(devs)} devices are "
            "visible (set XLA_FLAGS=--xla_force_host_platform_device_count=K "
            "before importing jax to emulate K host devices)")
    return Mesh(np.asarray(devs[:n_shards]), (axis,))


@functools.partial(
    jax.jit, static_argnames=("n", "max_cdeg", "mesh", "axis"))
def _sharded_query_batch(
    eu, ev, esim, esim_t, emask,    # slot-sized, padded to k·⌈E/k⌉
    epos, elast,                    # per-shard row layout
    no_sims,                        # NO-slot-sized, padded likewise
    offsets_c, cdeg, mus, epss,     # replicated (O(n) / parameter vectors)
    *, n: int, max_cdeg: int, mesh: Mesh, axis: str,
):
    from repro.core.query import cluster_labels

    steps = row_steps(max_cdeg - 1)

    def one(mu, eps, eu, ev, esim, esim_t, emask, epos, elast, no_s):
        mu = jnp.asarray(mu, jnp.int32)
        eps = jnp.asarray(eps, jnp.float32)

        # ---- cores: θ(v, μ) from the NO slot this shard holds ----
        chunk = no_s.shape[0]
        slot = offsets_c[:-1] + (mu - 1) - jax.lax.axis_index(axis) * chunk
        mine = (slot >= 0) & (slot < chunk)
        local = jnp.where(mine, no_s[jnp.clip(slot, 0, chunk - 1)],
                          -jnp.inf)
        theta = jax.lax.pmax(local, axis)
        is_core = (mu >= 2) & (cdeg >= mu) & (theta >= eps)

        rows = RowLayout(epos, elast.reshape(-1), steps)
        labels, n_clusters, rounds = cluster_labels(
            n, is_core, eu, ev, esim, esim_t, eps, rows, emask=emask,
            axis_name=axis)
        return labels, is_core, n_clusters, rounds

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis),) * 6 + (P(axis, None), P(axis))
        + (P(None),) * 4,
        out_specs=(P(None), P(None), P(None), P(None)),
        check_vma=False,
    )
    def _shard(eu, ev, esim, esim_t, emask, epos, elast, no_s,
               offsets_c, cdeg, mus, epss):
        return jax.vmap(
            lambda m, e: one(m, e, eu, ev, esim, esim_t, emask, epos,
                             elast, no_s)
        )(mus, epss)

    return _shard(eu, ev, esim, esim_t, emask, epos, elast, no_sims,
                  offsets_c, cdeg, mus, epss)


def _pad_host(arr, total: int, fill, dtype) -> np.ndarray:
    """Pad a 1-D array to ``total`` entries with ``fill`` (host numpy, so
    per-shard chunks can be diffed against a predecessor plan)."""
    arr = np.asarray(arr, dtype=dtype)
    pad = total - arr.shape[0]
    if pad == 0:
        return arr
    return np.concatenate([arr, np.full((pad,), fill, dtype=dtype)])


class ShardedQueryPlan:
    """Padded, device-placed operands for repeated sharded queries over one
    (index, graph, mesh) triple.

    Padding and concatenating the O(m) edge/NO-slot arrays is per-*plan*
    work, not per-*query* work: the serve engine answers a flush every few
    milliseconds against a fixed index, so it builds the plan once at
    registration and every device call is just the jitted shard_map
    computation over already-sharded arrays. ``query_batch_sharded`` builds
    a throwaway plan for one-shot callers.

    Ragged edge counts are padded host-side to a multiple of the axis size;
    padding edges carry ``emask=False`` and sit past every row's last
    slot, and padded NO slots are never a vertex's θ slot, so they never
    contribute. The plan also carries each slot's reverse-slot σ and the
    per-shard row layout (:class:`repro.core.connectivity.RowLayout`).

    :meth:`refresh` derives a successor plan after an incremental index
    update: per-shard chunks of each O(m) operand are compared host-side
    (sha256 content digests — 32 bytes per chunk retained, not the O(m)
    padded arrays themselves) and only *mutated* partitions are re-placed
    on device; unchanged shards — and the replicated O(n) vectors — adopt
    the old plan's buffers (an incremental edit batch typically touches a
    handful of partitions, not all k). ``refresh`` is loop-free pure
    compute, so the live-update path runs it in the engine's offload
    worker alongside ``apply_delta``.

    ``registry`` (a :class:`repro.obs.MetricsRegistry`) makes plan
    maintenance measurable per shard: each per-device chunk placement is
    timed into the ``sharded.place_chunk`` histogram (full-array initial
    placements into ``sharded.place_full``), reused vs re-placed chunks
    count into ``sharded.chunks_reused`` / ``sharded.chunks_placed``,
    and the whole build/refresh lands in ``sharded.plan_build``. The
    serve engine passes its registry through, so a hot-swap's refresh
    cost shows up next to the query latency it protects.
    """

    _SHARDED = ("emask", "eu", "ev", "esim", "esim_t", "epos", "elast",
                "no_sims")
    _REPLICATED = ("offsets_c", "cdeg")

    def __init__(self, index, g: CSRGraph, mesh: Mesh, axis: str = "data",
                 *, registry=None,
                 _reuse_from: "ShardedQueryPlan | None" = None):
        t_build = time.monotonic()
        self.mesh = mesh
        self.axis = axis
        self.n = index.n
        self.max_cdeg = index.max_cdeg
        self._registry = (registry if registry is not None
                          else getattr(_reuse_from, "_registry", None))
        k = mesh.shape[axis]
        self._shard = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())

        ep = max(-(-max(g.m2, 1) // k) * k, k)   # edge slots per full array
        chunk = ep // k
        m_no = index.no_sims.shape[0]
        cp = max(-(-max(m_no, 1) // k) * k, k)
        offsets = np.asarray(g.offsets, np.int64)
        eu = np.asarray(g.edge_u, np.int64)
        nbrs = np.asarray(g.nbrs, np.int64)
        esim = np.asarray(index.edge_sims, np.float32)
        slot = np.arange(g.m2, dtype=np.int64)
        # reverse slot of each half-edge: slots sorted by (v, u)
        twin = np.lexsort((eu, nbrs))
        # row layout per shard: a row cut by a shard boundary restarts at
        # the shard's first slot; each vertex's last slot inside a shard
        pos = slot - np.maximum(offsets[eu], slot - slot % chunk)
        lo_s = np.arange(k, dtype=np.int64)[:, None] * chunk
        first = np.maximum(offsets[None, :-1], lo_s)
        last = np.minimum(offsets[None, 1:], lo_s + chunk) - 1
        elast = np.where(last >= first, last - lo_s, -1)
        host = {   # transient: placed on device, digested, then dropped
            "emask": np.arange(ep) < g.m2,
            "eu": _pad_host(eu, ep, 0, np.int32),
            "ev": _pad_host(nbrs, ep, 0, np.int32),
            "esim": _pad_host(esim, ep, 0.0, np.float32),
            "esim_t": _pad_host(esim[twin], ep, 0.0, np.float32),
            "epos": _pad_host(pos, ep, 0, np.int32),
            "elast": elast.astype(np.int32),
            "no_sims": _pad_host(index.no_sims, cp, 0.0, np.float32),
        }
        self._chunk_digests: dict = {}
        stats = {"chunks": k * len(self._SHARDED), "reused": 0, "placed": 0,
                 "repl_reused": 0}
        for name in self._SHARDED:
            arr, reused = self._place(name, host[name], _reuse_from)
            setattr(self, name, arr)
            stats["reused"] += reused
            stats["placed"] += k - reused
        # the replicated O(n) vectors diff the same way the sharded chunks
        # do: unchanged content adopts the predecessor's buffer
        self._repl_digests = {}
        for name in self._REPLICATED:
            h = np.asarray(getattr(index, name))
            self._repl_digests[name] = (h.shape,
                                        hashlib.sha256(h.tobytes()).digest())
            if (_reuse_from is not None and _reuse_from.mesh is self.mesh
                    and _reuse_from._repl_digests.get(name)
                    == self._repl_digests[name]):
                setattr(self, name, getattr(_reuse_from, name))
                stats["repl_reused"] += 1
            else:
                setattr(self, name, jax.device_put(jnp.asarray(h), repl))
        self.last_refresh = stats
        if self._registry is not None:
            self._registry.inc("sharded.chunks_reused", stats["reused"])
            self._registry.inc("sharded.chunks_placed", stats["placed"])
            self._registry.observe("sharded.plan_build",
                                   time.monotonic() - t_build)

    def _place(self, name: str, host: np.ndarray,
               prev: "ShardedQueryPlan | None"):
        """Device-place one sharded operand, adopting the predecessor's
        per-shard buffers wherever the chunk content digest is unchanged.
        Returns (global array, number of reused chunks)."""
        k = self.mesh.shape[self.axis]
        chunk = host.shape[0] // k
        digests = [
            hashlib.sha256(
                np.ascontiguousarray(host[i * chunk:(i + 1) * chunk])
                .tobytes()).digest()
            for i in range(k)]
        self._chunk_digests[name] = (host.shape, digests)
        if (prev is None or prev.mesh is not self.mesh
                or prev._chunk_digests[name][0] != host.shape):
            t0 = time.monotonic()
            arr = jax.device_put(jnp.asarray(host), self._shard)
            if self._registry is not None:
                self._registry.observe("sharded.place_full",
                                       time.monotonic() - t0)
            return arr, 0
        old_digests = prev._chunk_digests[name][1]
        old_arr = getattr(prev, name)
        by_start = {(s.index[0].start or 0): s.data
                    for s in old_arr.addressable_shards}
        devices = list(self.mesh.devices.flat)
        bufs, reused = [], 0
        for i in range(k):
            lo = i * chunk
            if old_digests[i] == digests[i]:
                bufs.append(by_start[lo])
                reused += 1
            else:
                t0 = time.monotonic()
                bufs.append(jax.device_put(
                    jnp.asarray(host[lo: lo + chunk]), devices[i]))
                if self._registry is not None:
                    # one sample per re-placed shard chunk: the per-shard
                    # cost of a hot-swap's operand refresh
                    self._registry.observe("sharded.place_chunk",
                                           time.monotonic() - t0)
        arr = jax.make_array_from_single_device_arrays(
            host.shape, self._shard, bufs)
        return arr, reused

    def refresh(self, index, g: CSRGraph) -> "ShardedQueryPlan":
        """Successor plan for an updated (index, graph): re-shards only
        the mutated partitions of the O(m) operands (see
        ``plan.last_refresh`` for the reuse/placed chunk counts). The old
        plan is left untouched, so an engine can serve in-flight traffic
        against it until the hot-swap completes."""
        return ShardedQueryPlan(index, g, self.mesh, self.axis,
                                _reuse_from=self)

    def __call__(self, mus, epss):
        from repro.core.query import ClusterResult

        mus = jnp.atleast_1d(jnp.asarray(mus, jnp.int32))
        epss = jnp.atleast_1d(jnp.asarray(epss, jnp.float32))
        labels, is_core, n_clusters, rounds = _sharded_query_batch(
            self.eu, self.ev, self.esim, self.esim_t, self.emask,
            self.epos, self.elast, self.no_sims,
            self.offsets_c, self.cdeg, mus, epss,
            n=self.n, max_cdeg=self.max_cdeg, mesh=self.mesh,
            axis=self.axis)
        return ClusterResult(labels=labels, is_core=is_core,
                             n_clusters=n_clusters, rounds=rounds)


def query_batch_sharded(
    index,
    g: CSRGraph,
    mus,
    epss,
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
):
    """Sharded twin of :func:`repro.core.query_batch`.

    Partitions the half-edge arrays (endpoints, similarities) and the CO
    slot arrays over ``mesh``'s ``axis``; returns the exact same
    ``ClusterResult`` (leading batch axis) as the single-device path.
    Repeated callers should build a :class:`ShardedQueryPlan` once instead.
    """
    if mesh is None:
        mesh = query_mesh(axis=axis)
    return ShardedQueryPlan(index, g, mesh, axis)(mus, epss)
