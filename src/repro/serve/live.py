"""Resident index construction + update + query process.

``LiveIndexService`` completes the serve story for *evolving* graphs: one
process owns the named indexes (an :class:`~repro.serve.store.IndexCatalog`
on disk), serves (μ, ε) queries through the micro-batching router, and
applies :class:`~repro.core.update.EdgeDelta` batches between engine
flushes — no cold rebuilds, no process restarts.

Approximate-first ingest (paper §5–§6.3): ``register_approximate`` builds
an LSH-sketched index (cheap — sketches + the §6.3 degree-heuristic exact
pass) and serves it *immediately*; ``refine`` then builds the exact index
on the engine's offload worker while the approximate one keeps answering,
and hot-swaps it in behind the same ``drain()`` barrier deltas use.
Every index carries an :class:`~repro.core.approx.IndexProvenance` tag
(exact vs approx + sketch params) that persists with snapshots and is
queryable per route, so a crash before the refine swap restores the
*approximate* index — the service degrades to provably-close answers,
never to downtime — and a crash after it restores exact.

Update protocol (per named index):

  1. ``apply_delta`` maintains the index incrementally (bit-identical to a
     rebuild — see ``repro.core.update``); the old (index, graph) pair is
     untouched. The apply runs **off the event loop** (the engine's
     single-worker ``offload_executor()``), so the collector keeps
     flushing query batches while the delta is being absorbed — apply
     latency never appears in query tail latency.
  2. The delta is appended to the on-disk chain
     (:class:`~repro.serve.store.DeltaLog`) *before* the swap — a crash
     after the append replays the delta on restart; a crash during it
     leaves an ignorable ``.tmp`` and the previous version restorable.
     (The append happens in the same worker job as the apply.)
  3. The new index registers with the engine under its new content
     fingerprint (in sharded mode, via ``ShardedQueryPlan.refresh`` —
     also run in the worker — so only mutated partitions of the O(m)
     operands are re-placed on device), then the name's route flips in
     one assignment *on the loop* — queries that already resolved the old
     fingerprint keep hitting the old index, new queries hit the new one,
     and *nobody* sees a mix.
  4. ``engine.drain()`` barriers until every in-flight request has been
     answered, then the old fingerprint unregisters — which also drops
     exactly its cache partition (sibling indexes keep their hit rates;
     that is the whole point of fingerprint-keyed invalidation).
  5. Recently observed (μ, ε) settings are re-issued against the new
     index, which re-warms their (μ±1, ε±δ) neighborhood through the
     engine's padding-slot warming.
  6. Every ``compact_every`` deltas the live index is saved as a full
     snapshot (version = delta seq, written in the offload worker — the
     O(m) disk write never stalls the collector either) and the covered
     chain prefix is pruned; restore = latest snapshot + replay of the
     strictly-newer tail, fingerprint-verified step by step.

Every stage of that protocol is traced through the engine's
``repro.obs`` tracer (span == same-named latency histogram): a
``live.apply`` root span wraps each delta; inside it the worker records
``live.apply_delta`` (with the ``UpdateInfo`` work counters — frontier
size, ``n_sim_groups``, ``n_plan_rows``, ``n_plan_classes`` — as span
attributes), ``live.fingerprint``, ``live.shard_refresh``, and
``live.log_append``; back on the loop ``live.swap`` (register + route
flip), ``live.drain`` (the barrier), ``live.rewarm``, and
``live.compact`` when compaction triggers. The engine's
``engine.offload_depth`` gauge exposes how many maintenance jobs are
queued behind the single worker. This is how the PR 5 claim — "apply
latency never shows in query tails" — became a measurement: apply spans
record nonzero durations while the concurrent ``engine.e2e`` histogram
keeps filling (asserted in tests/test_serve_obs.py).
"""
from __future__ import annotations

import asyncio
import dataclasses
import logging
from collections import OrderedDict
from typing import Dict, List, Optional

from repro.backend.policy import ExecutionPolicy
from repro.backend.profile import DEFAULT_PROFILE, AutotuneProfile
from repro.core.approx import (EXACT_PROVENANCE, ApproxIndexBuilder,
                               ApproxParams, IndexProvenance)
from repro.core.graph import CSRGraph
from repro.core.index import ScanIndex, build_index
from repro.core.query import ClusterResult
from repro.core.update import EdgeDelta, UpdateInfo, apply_delta
from repro.serve.cache import quantize_eps
from repro.serve.engine import EngineConfig, MicroBatchEngine
from repro.serve.store import DeltaLog, IndexCatalog, index_fingerprint


def _log_abandoned_apply(task) -> None:
    """Surface the outcome of an apply whose caller cancelled mid-commit."""
    if task.cancelled():
        return
    exc = task.exception()
    if exc is not None:
        logging.getLogger(__name__).error(
            "abandoned live-index apply failed: %r", exc)


@dataclasses.dataclass(frozen=True)
class _Live:
    """One name's resident state (replaced wholesale on every swap)."""

    index: ScanIndex
    g: CSRGraph
    fp: str
    seq: int            # last applied delta sequence number
    snapshot_seq: int   # delta seq covered by the newest full snapshot
    provenance: IndexProvenance = EXACT_PROVENANCE
    # the autotune profile the newest snapshot was persisted with; when it
    # differs from the serving policy's profile, profile_mismatch flags it
    # in status() — serving continues on the policy thresholds (lane
    # choice never moves index bits), never silently retunes
    profile: AutotuneProfile = DEFAULT_PROFILE
    profile_mismatch: bool = False


class LiveIndexService:
    """Named live indexes behind one micro-batching engine.

    ``measure`` is the structural-similarity measure every index in this
    service is built and maintained with.
    """

    def __init__(self, root: str, *,
                 config: EngineConfig = EngineConfig(),
                 measure: str = "cosine",
                 compact_every: int = 8,
                 keep_snapshots: int = 3,
                 rewarm_recent: int = 4,
                 policy: Optional[ExecutionPolicy] = None):
        self.catalog = IndexCatalog(root, keep=keep_snapshots)
        self.engine = MicroBatchEngine(config=config, policy=policy)
        self.measure = measure
        self.compact_every = compact_every
        self.rewarm_recent = rewarm_recent
        self._live: Dict[str, _Live] = {}
        self._observed: Dict[str, OrderedDict] = {}
        self._locks: Dict[str, asyncio.Lock] = {}
        self._pending: set = set()   # in-flight (possibly abandoned) applies

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    async def __aenter__(self) -> "LiveIndexService":
        await self.engine.start()
        return self

    async def __aexit__(self, *exc) -> None:
        # a cancellation-shielded apply may have outlived its caller; its
        # swap continuation must finish *before* the engine stops, or it
        # would register into (and re-warm against) a dead router
        while self._pending:
            await asyncio.gather(*tuple(self._pending),
                                 return_exceptions=True)
        await self.engine.stop()

    def names(self) -> List[str]:
        return sorted(self._live)

    def fingerprint(self, name: str) -> str:
        return self._live[name].fp

    def index(self, name: str) -> ScanIndex:
        """The currently live index for ``name``."""
        return self._live[name].index

    def graph(self, name: str) -> CSRGraph:
        """The currently live graph for ``name``."""
        return self._live[name].g

    def status(self, name: str) -> dict:
        """Version/routing state for ``name`` (fp, seq, snapshot_seq,
        provenance) plus the ``backend`` execution block: platform,
        forced lane, the lane each op resolves to right now, the active
        autotune profile — and, when the stored snapshot was persisted
        under a *different* profile, ``profile_mismatch`` with the stored
        thresholds (serving stays on the policy's; bit-identity across
        lanes makes that safe, and we never silently retune)."""
        live = self._live[name]
        backend = self.engine.policy.describe()
        backend["profile_mismatch"] = live.profile_mismatch
        if live.profile_mismatch:
            backend["stored_profile"] = dataclasses.asdict(live.profile)
        return {"fingerprint": live.fp, "seq": live.seq,
                "snapshot_seq": live.snapshot_seq,
                "n": live.g.n, "m": live.g.m,
                "provenance": live.provenance.describe(),
                "approx": live.provenance.is_approx,
                "backend": backend}

    def provenance(self, name: str) -> IndexProvenance:
        """How ``name``'s currently served similarities were produced."""
        return self._live[name].provenance

    def stats(self) -> dict:
        out = self.engine.batch_stats()
        out["live_indexes"] = len(self._live)
        out["live_seqs"] = {n: l.seq for n, l in self._live.items()}
        return out

    # ------------------------------------------------------------------
    # index creation / restore
    # ------------------------------------------------------------------
    def create(self, name: str, g: CSRGraph, *,
               index: Optional[ScanIndex] = None,
               provenance: Optional[IndexProvenance] = None) -> str:
        """Build (or adopt) an index for ``name``, persist snapshot v0,
        register it with the engine; → fingerprint. ``provenance`` tags an
        adopted index (default: exact).

        Traced as ``live.create`` ⊃ ``index.build`` (when built here),
        ``live.fingerprint``, ``store.save`` and ``engine.register``."""
        if name in self._live:
            raise ValueError(f"index {name!r} already live")
        tracer = self.engine.tracer
        with tracer.span("live.create", index=name, n=g.n, m=g.m):
            if index is None:
                index = build_index(g, self.measure, tracer=tracer)
            if provenance is None:
                provenance = EXACT_PROVENANCE
            with tracer.span("live.fingerprint", index=name):
                fp = index_fingerprint(index, g)
            profile = self.engine.policy.profile
            with tracer.span("store.save", index=name):
                self.catalog.store(name).save(index, g, version=0,
                                              measure=self.measure,
                                              provenance=provenance,
                                              profile=profile)
            with tracer.span("engine.register", index=name):
                self.engine.register(index, g, fingerprint=fp,
                                     provenance=provenance)
        self._live[name] = _Live(index=index, g=g, fp=fp, seq=0,
                                 snapshot_seq=0, provenance=provenance,
                                 profile=profile)
        return fp

    def register_approximate(self, name: str, g: CSRGraph, *,
                             params: ApproxParams = ApproxParams()) -> str:
        """Approximate-first ingest: build an LSH-sketched index for
        ``name`` (fast — the paper's §5/§6.3 construction), persist it as
        snapshot v0 *with its approx provenance*, and start serving from
        it immediately; → fingerprint.

        The index answers queries with σ̂ instead of σ (provably close —
        Theorems 5.2/5.3; exact on every §6.3 low-degree edge). Call
        :meth:`refine` afterwards to build the exact index in the
        background and hot-swap it in. A crash before the refine swap
        restores this approximate index from the store (its provenance
        travels with the snapshot), so the service degrades to
        approximate answers, never to downtime.
        """
        if name in self._live:
            raise ValueError(f"index {name!r} already live")
        builder = ApproxIndexBuilder(self.measure, params,
                                     policy=self.engine.policy)
        index, provenance = builder.build(g, tracer=self.engine.tracer)
        return self.create(name, g, index=index, provenance=provenance)

    def load(self, name: str) -> str:
        """Restore ``name`` from disk: latest snapshot + delta-chain tail
        (each replayed step fingerprint-verified); → fingerprint."""
        if name in self._live:
            raise ValueError(f"index {name!r} already live")
        store = self.catalog.store(name)
        index, g, fp = store.load()
        provenance = store.provenance()
        stored_profile = store.profile()
        profile_mismatch = stored_profile != self.engine.policy.profile
        if profile_mismatch:
            # surfaced in status() rather than retuned: thresholds only
            # steer lane choice, and every lane is bit-identical, so the
            # restored index serves correctly on the policy's profile
            logging.getLogger(__name__).warning(
                "index %r: snapshot autotune profile differs from the "
                "serving policy's; serving on policy thresholds", name)
        stored_measure = store.measure()
        if stored_measure is not None and stored_measure != self.measure:
            raise ValueError(
                f"index {name!r} was built with measure "
                f"{stored_measure!r}; this service maintains "
                f"{self.measure!r} — frontier σ recomputes would silently "
                "mix measures")
        snap_seq = store.latest_version()
        log = DeltaLog(store.directory)
        # a crash mid-append can leave a renamed-but-torn tail entry
        # (pre-durability writers; torn bytes). This service *owns* the
        # chain, so recovery truncates it and replay lands on the last
        # intact entry — the delta it described was never served anyway
        torn = log.truncate_torn_tail()
        if torn:
            logging.getLogger(__name__).warning(
                "index %r: truncated torn delta-chain tail %s", name, torn)
        seq = snap_seq
        for s in log.sequences():
            if s <= snap_seq:
                continue
            if s != seq + 1:
                raise ValueError(
                    f"delta chain for {name!r} has a gap: snapshot at "
                    f"{snap_seq}, next delta {s} after {seq}")
            delta, want_fp = log.load(s)
            index, g, _ = apply_delta(index, g, delta, self.measure)
            fp = index_fingerprint(index, g)
            if fp != want_fp:
                raise ValueError(
                    f"delta {s} for {name!r} replayed to fingerprint "
                    f"{fp[:12]}… but the chain recorded {want_fp[:12]}…")
            seq = s
        self.engine.register(index, g, fingerprint=fp,
                             provenance=provenance)
        self._live[name] = _Live(index=index, g=g, fp=fp, seq=seq,
                                 snapshot_seq=snap_seq,
                                 provenance=provenance,
                                 profile=stored_profile,
                                 profile_mismatch=profile_mismatch)
        return fp

    def load_all(self) -> List[str]:
        for name in self.catalog.names():
            if name not in self._live:
                self.load(name)
        return self.names()

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------
    async def query(self, name: str, mu: int, eps: float) -> ClusterResult:
        """One SCAN query by *name*; the route resolves atomically here,
        so a concurrent hot-swap gives this query entirely the old or
        entirely the new index."""
        live = self._live[name]
        self._note(name, mu, eps)
        return await self.engine.query(mu, eps, fingerprint=live.fp)

    async def query_seed(self, name: str, seed: int, mu: int, eps: float):
        """One seed-set (local) query by *name*: the cluster containing
        ``seed`` at (μ, ε) — a :class:`~repro.core.local.SeedResult`,
        bit-identical to the seed's row of the full :meth:`query` answer
        against the same live index."""
        live = self._live[name]
        return await self.engine.query_seed(seed, mu, eps,
                                            fingerprint=live.fp)

    def _note(self, name: str, mu: int, eps: float) -> None:
        obs = self._observed.setdefault(name, OrderedDict())
        key = (int(mu), quantize_eps(eps, self.engine.cfg.eps_quantum))
        obs.pop(key, None)
        obs[key] = True
        while len(obs) > self.rewarm_recent:
            obs.popitem(last=False)

    # ------------------------------------------------------------------
    # updates
    # ------------------------------------------------------------------
    async def apply(self, name: str, delta: EdgeDelta) -> UpdateInfo:
        """Apply one edit batch to ``name`` and hot-swap the result in.

        The expensive, loop-irrelevant work — ``apply_delta``, the content
        fingerprint, the crash-safe ``DeltaLog`` append, and (in sharded
        mode) the mutated-partition-only ``ShardedQueryPlan.refresh`` —
        runs in the engine's offload worker, so the collector keeps
        flushing query batches against the *old* index for the whole
        duration. Only the swap itself (register, route flip, drain,
        unregister, re-warm) runs on the event loop.

        An apply is a *commit*: the whole sequence is shielded from
        caller cancellation (e.g. ``asyncio.wait_for`` timeouts), because
        the executor job cannot be interrupted once launched — abandoning
        the coroutine mid-way would leave the on-disk delta chain one
        committed entry ahead of the served in-memory state (and a
        successor apply would silently reuse its sequence number). The
        caller still observes ``CancelledError``; the swap completes in
        the background regardless (``__aexit__`` waits for abandoned
        applies before stopping the engine).
        """
        task = asyncio.ensure_future(self._apply_locked(name, delta))
        self._pending.add(task)
        task.add_done_callback(self._pending.discard)
        try:
            return await asyncio.shield(task)
        except asyncio.CancelledError:
            # the caller walked away from a commit in flight — its
            # eventual outcome must not vanish (a failure would otherwise
            # surface only as a gc-time 'never retrieved' warning);
            # callers that kept awaiting get the exception via the shield
            # and are responsible for it themselves
            task.add_done_callback(_log_abandoned_apply)
            raise

    async def _apply_locked(self, name: str, delta: EdgeDelta) -> UpdateInfo:
        lock = self._locks.setdefault(name, asyncio.Lock())
        tracer = self.engine.tracer
        async with lock:
            live = self._live[name]
            seq = live.seq + 1
            log_dir = self.catalog.store(name).directory

            def _absorb():
                # worker-side spans nest under live.apply: run_offloaded
                # ships the caller's contextvars into the worker thread
                with tracer.span("live.apply_delta", index=name,
                                 seq=seq) as sp:
                    new_index, new_g, info = apply_delta(
                        live.index, live.g, delta, self.measure)
                    sp.set(n_inserted=info.n_inserted,
                           n_deleted=info.n_deleted,
                           n_touched=info.n_touched,
                           n_frontier=info.n_frontier,
                           n_affected_rows=info.n_affected_rows,
                           n_sim_groups=info.n_sim_groups,
                           n_plan_rows=info.n_plan_rows,
                           n_plan_classes=info.n_plan_classes)
                with tracer.span("live.fingerprint", index=name):
                    new_fp = index_fingerprint(new_index, new_g)
                shard_plan = None
                # look the predecessor plan up *here*, not before the
                # worker started: the collector may lazily build it for
                # the old fingerprint while this apply is in flight
                old_plan = self.engine._shard_plans.get(live.fp)
                if old_plan is not None and new_fp != live.fp:
                    # re-shard only the mutated partitions; the old plan
                    # stays intact for in-flight traffic until the drain
                    with tracer.span("live.shard_refresh",
                                     index=name) as sp:
                        shard_plan = old_plan.refresh(new_index, new_g)
                        sp.set(**shard_plan.last_refresh)
                # commit to the chain *last*: a failure anywhere above
                # must not leave the on-disk log ahead of served state
                # (the next apply would reuse this sequence number)
                with tracer.span("live.log_append", index=name, seq=seq):
                    DeltaLog(log_dir).append(seq, delta, new_fp)
                return new_index, new_g, info, new_fp, shard_plan

            with tracer.span("live.apply", index=name, seq=seq) as apply_sp:
                new_index, new_g, info, new_fp, shard_plan = \
                    await self.engine.run_offloaded(_absorb)
                apply_sp.set(swapped=new_fp != live.fp,
                             n_frontier=info.n_frontier)

                if new_fp != live.fp:
                    with tracer.span("live.swap", index=name):
                        # provenance carries across deltas: frontier σ is
                        # recomputed exactly, but untouched edges keep
                        # their sketched σ̂ — the index stays approximate
                        # until refine() replaces it wholesale
                        self.engine.register(new_index, new_g,
                                             fingerprint=new_fp,
                                             shard_plan=shard_plan,
                                             provenance=live.provenance)
                        # seed-cache frontier invalidation: entries whose
                        # seed *and* members all avoid the delta's stale
                        # set are bit-identical under the new index —
                        # carry them to the new fingerprint instead of
                        # recomputing; the rest are dropped here (and the
                        # old partition's remainder goes with the
                        # unregister below)
                        kept, dropped = self.engine.seed_cache.migrate(
                            live.fp, new_fp, info.stale_mask(new_g.n))
                        self.engine.registry.inc(
                            "live.seed_entries_migrated", kept)
                        self.engine.registry.inc(
                            "live.seed_entries_dropped", dropped)
                        self._live[name] = dataclasses.replace(
                            live, index=new_index, g=new_g, fp=new_fp,
                            seq=seq)
                    with tracer.span("live.drain", index=name):
                        await self.engine.drain()
                    if live.fp not in {l.fp for l in self._live.values()}:
                        self.engine.unregister(live.fp)
                    with tracer.span("live.rewarm", index=name):
                        await self._rewarm(name)
                else:
                    self._live[name] = dataclasses.replace(
                        live, index=new_index, g=new_g, fp=new_fp, seq=seq)
                if seq - self._live[name].snapshot_seq >= self.compact_every:
                    # the O(m) snapshot write is disk work on an immutable
                    # (index, graph) pair — it belongs in the worker too,
                    # not on the loop stalling the collector
                    def _compact():
                        with tracer.span("live.compact", index=name):
                            self.compact(name)
                    await self.engine.run_offloaded(_compact)
            return info

    # ------------------------------------------------------------------
    # background exact refinement (approximate-first lifecycle)
    # ------------------------------------------------------------------
    async def refine(self, name: str) -> str:
        """Replace ``name``'s approximate index with the exact build, off
        the event loop; → the fingerprint served afterwards.

        The exact ``build_index`` (the expensive part — it is exactly the
        work approximate-first ingest deferred) runs in the engine's
        single-worker ``offload_executor()``, so the collector keeps
        answering queries from the approximate index for the whole build.
        The swap then follows the same protocol as a delta hot-swap:
        register the exact index under its new fingerprint, flip the route
        in one assignment on the loop, ``drain()`` until every in-flight
        request has answered (old or new, never a mix), unregister the
        approximate fingerprint — which drops exactly its cache partition —
        and re-warm observed traffic. Finally the exact index is persisted
        as a full snapshot (off-loop), so a restart serves exact without
        re-refining.

        Failure is graceful by construction: the approximate index is not
        touched until the exact build has fully succeeded, so an exception
        in the worker leaves it serving (counted in the
        ``live.refine_failures`` registry counter) and the caller may
        retry. Refines serialize with :meth:`apply` on the per-name lock —
        a delta landing mid-build would otherwise be silently discarded by
        the swap.

        Refining an already-exact index is a no-op returning the current
        fingerprint.
        """
        lock = self._locks.setdefault(name, asyncio.Lock())
        tracer = self.engine.tracer
        async with lock:
            live = self._live[name]
            if not live.provenance.is_approx:
                return live.fp
            seq = live.seq + 1

            def _build_exact():
                with tracer.span("live.refine_build", index=name,
                                 n=live.g.n, m=live.g.m):
                    new_index = build_index(live.g, self.measure)
                with tracer.span("live.fingerprint", index=name):
                    new_fp = index_fingerprint(new_index, live.g)
                shard_plan = None
                old_plan = self.engine._shard_plans.get(live.fp)
                if old_plan is not None and new_fp != live.fp:
                    with tracer.span("live.shard_refresh", index=name) as sp:
                        shard_plan = old_plan.refresh(new_index, live.g)
                        sp.set(**shard_plan.last_refresh)
                return new_index, new_fp, shard_plan

            with tracer.span("live.refine", index=name, seq=seq) as ref_sp:
                try:
                    new_index, new_fp, shard_plan = \
                        await self.engine.run_offloaded(_build_exact)
                except Exception:
                    # graceful degradation: the approximate index was never
                    # deregistered, so traffic keeps flowing against it
                    self.engine.registry.inc("live.refine_failures")
                    ref_sp.set(failed=True)
                    raise
                ref_sp.set(swapped=new_fp != live.fp)

                if new_fp != live.fp:
                    with tracer.span("live.swap", index=name):
                        self.engine.register(new_index, live.g,
                                             fingerprint=new_fp,
                                             shard_plan=shard_plan,
                                             provenance=EXACT_PROVENANCE)
                        self._live[name] = dataclasses.replace(
                            live, index=new_index, fp=new_fp, seq=seq,
                            provenance=EXACT_PROVENANCE)
                    with tracer.span("live.drain", index=name):
                        await self.engine.drain()
                    if live.fp not in {l.fp for l in self._live.values()}:
                        self.engine.unregister(live.fp)
                    with tracer.span("live.rewarm", index=name):
                        await self._rewarm(name)
                else:
                    # sketch happened to reproduce exact σ bit-for-bit
                    # (tiny graphs / pure-heuristic edges): relabel the
                    # provenance only. Re-register()ing the same
                    # fingerprint would take the hot-swap path and throw
                    # away the route's shard plan plus two cache
                    # partitions full of answers that are — by the very
                    # premise of this branch — still bit-identical.
                    self.engine.relabel(live.fp,
                                        provenance=EXACT_PROVENANCE)
                    self._live[name] = dataclasses.replace(
                        live, seq=seq, provenance=EXACT_PROVENANCE)

                # persist the refined index as a full snapshot covering
                # ``seq`` — version numbers stay monotone with delta seqs,
                # so restore = this snapshot + strictly-newer chain tail.
                # The O(m) disk write is worker work, not loop work.
                def _snapshot():
                    with tracer.span("live.compact", index=name):
                        self.compact(name)
                await self.engine.run_offloaded(_snapshot)
            return self._live[name].fp

    async def _rewarm(self, name: str) -> None:
        """Re-issue the recently observed settings against the fresh
        index — the engine's padding-slot warming re-warms their
        (μ±1, ε±δ) neighborhood as a side effect.

        Warming is best-effort by definition: it runs *after* the
        delta/refine has committed and the route has flipped, so a
        failed warm query must neither cancel its siblings nor
        propagate — the caller's apply succeeded, and raising here would
        make a completed commit look failed. Failures land in the
        ``live.rewarm_failures`` counter instead."""
        if not self.engine.is_running:
            # engine already stopped (an abandoned apply finishing late):
            # warming would auto-start a collector on a dying loop
            return
        fp = self._live[name].fp
        obs = list(self._observed.get(name, ()))
        if obs:
            results = await asyncio.gather(
                *[self.engine.query(mu, eps, fingerprint=fp)
                  for mu, eps in obs],
                return_exceptions=True)
            failures = sum(1 for r in results
                           if isinstance(r, BaseException))
            if failures:
                self.engine.registry.inc("live.rewarm_failures", failures)

    # ------------------------------------------------------------------
    # compaction
    # ------------------------------------------------------------------
    def compact(self, name: str) -> int:
        """Save the live index as a full snapshot (version = delta seq)
        and prune the covered chain prefix; → pruned delta count."""
        live = self._live[name]
        store = self.catalog.store(name)
        profile = self.engine.policy.profile
        store.save(live.index, live.g, version=live.seq,
                   measure=self.measure, provenance=live.provenance,
                   profile=profile)
        dropped = DeltaLog(store.directory).prune_through(live.seq)
        # the fresh snapshot carries the serving policy's profile, so any
        # restored-from-an-older-profile mismatch is resolved here
        self._live[name] = dataclasses.replace(
            live, snapshot_seq=live.seq, profile=profile,
            profile_mismatch=False)
        return dropped
