"""Async micro-batching query engine with multi-index routing.

Concurrent clients each want one (μ, ε) answer — possibly against
*different* graphs; the device wants one big fixed-shape vmapped call per
index. The engine is the adapter: requests land on an asyncio queue tagged
with the fingerprint of the index they address, a collector coroutine
drains them until either ``max_batch`` requests are waiting or ``flush_ms``
has elapsed since the first one (classic size-or-deadline micro-batching),
then **buckets the batch by fingerprint** and answers each bucket with its
own ``query_batch`` call against that bucket's index.

Routing mechanics (one engine process, many indexes):

* **registration** — ``register(index, g)`` keys the index by its content
  fingerprint (``serve/store.py``); ``query(μ, ε, fingerprint=...)``
  routes to it. Engines constructed the classic way — one index — keep the
  old single-index API: ``query(μ, ε)`` goes to the sole registered index.
* **per-index cache partitions** — the default cache is a
  ``PartitionedResultCache``: every fingerprint gets its own LRU, so one
  hot index cannot evict another's entries, and unregistering an index
  drops its partition wholesale.
* **dedup never aliases across indexes** — the dedup/cache key is
  (fingerprint, μ, quantized ε); identical (μ, ε) against two indexes are
  distinct slots in distinct buckets.
* **failure isolation per bucket** — a failing device call rejects only
  that bucket's futures; other buckets in the same flush, and the
  collector itself, are unaffected.

Throughput mechanics (unchanged from the single-index engine):

* **dedup** — concurrent identical requests (after ε quantization) fold
  into one batch slot; every waiter gets the same result object.
* **cache** — answers are LRU-cached on (fingerprint, μ, quantized ε);
  hits resolve without touching the device.
* **fixed batch shape** — each bucket's device call is always padded to
  ``max_batch`` slots, so exactly one XLA artifact per index serves every
  traffic pattern; no recompiles mid-flight.
* **sweep-ahead warming** — padding slots are filled with the (μ±1, ε±δ)
  neighborhood of the bucket's real requests instead of dead repeats
  (``serve.cache.neighborhood``): parameter-exploring clients walk the
  grid locally, so the next request is usually already cached by the time
  it arrives. Warming changes neither the batch shape nor the call count —
  it rides slots that were previously wasted.
* **sharded execution** — ``EngineConfig(shards=k)`` runs every device
  call through :func:`repro.core.query_batch_sharded` on a k-way mesh
  (giant-graph mode: edge arrays partitioned over the ``data`` axis).

Seed-set (local) queries are a second request kind on the same queue:
``await engine.query_seed(seed, μ, ε)`` answers "what is *this vertex's*
cluster" through :func:`repro.core.local.query_seeds` — work scales with
the output cluster, not with n. Seed requests get their **own buckets
and their own fixed batch shape** (``seed_batch`` lanes per device call,
padded the same way), so seed and global traffic never share a compiled
artifact; their dedup/cache key is (fingerprint, seed, μ, quantized ε)
in a dedicated :class:`~repro.serve.cache.SeedResultCache`, whose
entries survive live-index deltas when the seed's cluster provably
didn't change (``SeedResultCache.migrate`` — see ``serve/live.py``).
Padding slots warm the (μ±1, ε±δ) neighborhood of the *same seed*.
Seed telemetry mirrors the global taxonomy under ``engine.seed_*``:
``seed_e2e`` histogram, ``seed_queue_wait`` event, ``seed_cache_lookup``
/ ``seed_device_call`` spans, and ``seed_requests`` / ``seed_batches`` /
``seed_cache_hits`` / ``seed_deduped`` / ``seed_device_queries`` /
``seed_warmed`` / ``seed_spills`` counters.

The device call runs inline on the event loop: it is the serial resource
being scheduled, and everything else the loop does (queueing, cache hits)
is microseconds. Results are host-side numpy ``ClusterResult``s. Index
*maintenance* is the opposite case — ``apply_delta`` takes tens of
milliseconds and is not the resource queries wait on — so the engine
exposes a single-worker ``offload_executor()`` that ``LiveIndexService``
uses to apply + log deltas off the loop: collector flushes proceed during
an in-flight apply, and apply latency never shows up in query tails.

Telemetry (``repro.obs``): every engine owns a ``MetricsRegistry`` + a
``Tracer``. Per request the engine records an ``engine.cache_lookup``
span, an ``engine.queue_wait`` event (enqueue → flush pickup), and an
``engine.e2e`` histogram sample (request → resolved, cache hits
included); per flush an ``engine.batch_assembly`` event, an
``engine.flush`` span and, inside it, one ``engine.device_call`` span per
bucket, whose child ``engine.device_wait`` waits for the device before
the host copies (so a flush's host time is ``engine.flush`` minus
``engine.device_wait``); the counters ``query.rounds`` (propagation
rounds each call ran: its slowest lane's) and ``query.lane_rounds``
(summed over the call's slots); plus counters for every legacy
``stats`` key, an ``engine.queue_depth`` / ``engine.offload_depth``
gauge pair, and an ``engine.jit_recompiles`` counter fed by jit
cache-size deltas measured around each device call — a steady-state
engine that keeps retracing is a *measured* regression, not a silent
slowdown. ``engine.stats`` remains as a read-only mapping view over the
registry counters (the old mutable dict was updated from both the event
loop and the offload worker with no synchronization — a lost-update
bug; all mutations now go through the thread-safe registry).
"""
from __future__ import annotations

import asyncio
import contextvars
import dataclasses
import sys
import time
from collections.abc import Mapping
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import numpy as np

from repro.backend.policy import ExecutionPolicy
from repro.core.graph import CSRGraph
from repro.core.index import ScanIndex
from repro.core.local import SeedResult, query_seeds
from repro.core.query import ClusterResult, query_batch
from repro.obs import MetricsRegistry, Tracer
from repro.serve.admission import AdmissionConfig, AdmissionController
from repro.serve.cache import (DEFAULT_EPS_QUANTUM, PartitionedResultCache,
                               ResultCache, SeedResultCache, neighborhood,
                               quantize_eps)
from repro.serve.errors import EngineStopped
from repro.serve.store import index_fingerprint


# queue marker for drain() barriers — compared by identity, so no real
# fingerprint string can collide with it
_DRAIN = object()

# request kinds: queue items are (fp, kind, key, fut, t0); "q" keys are
# (μ, ε_q), "s" keys are (seed, μ, ε_q). Kinds bucket separately in
# _flush, so seed and global traffic never share a device call (nor a
# compiled artifact — their batch shapes differ).
_KIND_QUERY = "q"
_KIND_SEED = "s"

# legacy ``engine.stats`` keys, each backed by the registry counter
# ``engine.<key>``
_STAT_KEYS = ("requests", "batches", "device_queries", "cache_hits",
              "deduped", "warmed", "bucket_failures")


class _StatsView(Mapping):
    """Read-only mapping view of the engine's legacy counters, backed by
    the thread-safe registry. Reads are always current; writes must go
    through ``registry.inc`` (a ``stats[k] += 1`` raises TypeError, which
    is the point — the old dict was racily mutated from two threads)."""

    __slots__ = ("_registry",)

    def __init__(self, registry: MetricsRegistry) -> None:
        self._registry = registry

    def __getitem__(self, key: str) -> int:
        if key not in _STAT_KEYS:
            raise KeyError(key)
        return self._registry.counter(f"engine.{key}").value

    def __iter__(self):
        return iter(_STAT_KEYS)

    def __len__(self) -> int:
        return len(_STAT_KEYS)


def _query_jit_entries() -> int:
    """Total compiled-artifact count across the query path's jit caches
    (single-device ``query``/``query_batch`` + the sharded twin when
    loaded). The engine differences this around device calls: any growth
    after warmup is a retrace — e.g. an unhashed config field churning
    the cache key — surfaced as the ``engine.jit_recompiles`` counter."""
    import repro.core.query
    # the package re-exports ``query`` the *function*; go through
    # sys.modules for the submodule itself
    _query_mod = sys.modules["repro.core.query"]

    total = 0
    fns = [_query_mod.query, _query_mod.query_batch]
    local_mod = sys.modules.get("repro.core.local")
    if local_mod is not None:
        fns.append(local_mod.query_seeds_device)
    dist_mod = sys.modules.get("repro.core.distributed")
    if dist_mod is not None:
        fns.append(dist_mod._sharded_query_batch)
    for fn in fns:
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is not None:
            total += cache_size()
    return total


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 32          # device slots per micro-batch
    flush_ms: float = 2.0        # max wait after the first queued request
    cache_capacity: int = 4096   # per index partition
    eps_quantum: float = DEFAULT_EPS_QUANTUM
    warm_ahead: bool = True      # fill padding slots with (μ, ε) neighbors
    warm_eps_step: float = 0.05  # ε stride of the warmed neighborhood
    shards: Optional[int] = None  # run device calls sharded over k devices
    # --- seed-query lane (repro.core.local; single-device) ---
    seed_batch: int = 32          # device lanes per seed micro-batch
    seed_frontier_cap: int = 128  # member/frontier slots per lane (pow2)
    seed_window: int = 32         # NO-row ε-prefix entries per gather
    seed_border_cap: int = 512    # candidate-border slots per lane (pow2)
    # --- admission control (None = accept everything, the old behavior)
    admission: Optional[AdmissionConfig] = None
    # --- backend execution lane: None = auto-dispatch per call; one of
    # repro.backend.policy.LANES pins every kernel to that lane (the
    # REPRO_LANE env var overrides either way, per call)
    lane: Optional[str] = None


class MicroBatchEngine:
    """Serve one *or many* indexes to concurrent ``await engine.query(...)``.

    Single-index (classic): ``MicroBatchEngine(index, g)``.
    Multi-index (router):   ``MicroBatchEngine()`` then ``register(...)``
    per index; pass ``fingerprint=`` to ``query`` to route.
    """

    def __init__(self, index: Optional[ScanIndex] = None,
                 g: Optional[CSRGraph] = None, *,
                 fingerprint: Optional[str] = None,
                 config: EngineConfig = EngineConfig(),
                 cache=None,
                 registry: Optional[MetricsRegistry] = None,
                 policy: Optional[ExecutionPolicy] = None):
        self.cfg = config
        self.cache = cache if cache is not None else PartitionedResultCache(
            config.cache_capacity, config.eps_quantum)
        self.seed_cache = SeedResultCache(config.cache_capacity,
                                          config.eps_quantum)
        self._indexes: dict[str, tuple[ScanIndex, CSRGraph]] = {}
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        self._offload: Optional[ThreadPoolExecutor] = None
        self._mesh = None
        self._shard_plans: dict = {}   # fingerprint → ShardedQueryPlan
        self._provenance: dict = {}    # fingerprint → IndexProvenance
        self.registry = registry if registry is not None else MetricsRegistry()
        # the engine owns an ExecutionPolicy so every kernel-lane decision
        # made on its behalf lands in *its* registry (backend.* counters
        # sit next to engine.* in one scrape); config.lane forces a lane
        # for all ops, REPRO_LANE still overrides per call
        self.policy = (policy if policy is not None
                       else ExecutionPolicy(forced_lane=config.lane,
                                            registry=self.registry))
        if self.policy.registry is None:
            self.policy.registry = self.registry
        self.tracer = Tracer(self.registry)
        self.stats = _StatsView(self.registry)
        self.admission = (AdmissionController(config.admission, self.registry)
                          if config.admission is not None else None)
        self.fingerprint: Optional[str] = None
        if index is not None:
            if g is None:
                raise ValueError("an index needs its graph")
            self.fingerprint = self.register(index, g,
                                             fingerprint=fingerprint)

    # ------------------------------------------------------------------
    # index registry
    # ------------------------------------------------------------------
    def register(self, index: ScanIndex, g: CSRGraph, *,
                 fingerprint: Optional[str] = None,
                 shard_plan=None, provenance=None) -> str:
        """Add an index to the router; returns its routing fingerprint.

        ``shard_plan`` seeds the sharded-execution plan for this index
        (``EngineConfig(shards=k)`` mode) — the live-update hot-swap path
        hands over a plan refreshed from its predecessor so only mutated
        partitions of the O(m) operands were re-placed on device.

        ``provenance`` (a :class:`repro.core.approx.IndexProvenance`) tags
        the route with how the index's similarities were produced —
        approximate-first registrations advertise their sketch params here
        so clients/operators can see *what* a fingerprint answers with.
        """
        fp = (fingerprint if fingerprint is not None
              else index_fingerprint(index, g))
        if fp in self._indexes:
            # hot-swap under an explicit fingerprint: the old index's
            # sharded plan and cached answers (global *and* seed) must
            # not outlive it. A refine that reproduced the served bits
            # must NOT come through here — that is relabel()'s job.
            self._shard_plans.pop(fp, None)
            self.cache.invalidate(fp)
            self.seed_cache.invalidate(fp)
        self._indexes[fp] = (index, g)
        if shard_plan is not None:
            self._shard_plans[fp] = shard_plan
        if provenance is not None:
            self._provenance[fp] = provenance
        else:
            self._provenance.pop(fp, None)
        if self.fingerprint is None:
            self.fingerprint = fp
        return fp

    def unregister(self, fingerprint: str) -> int:
        """Drop an index and its cache partitions (global + seed);
        → evicted entry count."""
        self._indexes.pop(fingerprint, None)
        self._shard_plans.pop(fingerprint, None)
        self._provenance.pop(fingerprint, None)
        if self.fingerprint == fingerprint:
            self.fingerprint = next(iter(self._indexes), None)
        return (self.cache.invalidate(fingerprint)
                + self.seed_cache.invalidate(fingerprint))

    def relabel(self, fingerprint: str, *, provenance=None) -> None:
        """Update a registered route's provenance tag *only*.

        Unlike re-:meth:`register`-ing the same fingerprint (the hot-swap
        path), this leaves the compiled shard plan and both cache
        partitions intact — the right verb when a background refine
        reproduces the served index bit-for-bit and all that changed is
        how the bits were produced. ``provenance=None`` resets the route
        to the exact-build convention."""
        if fingerprint not in self._indexes:
            raise KeyError(
                f"no index registered for fingerprint {fingerprint!r}")
        if provenance is not None:
            self._provenance[fingerprint] = provenance
        else:
            self._provenance.pop(fingerprint, None)

    def provenance(self, fingerprint: Optional[str] = None):
        """The :class:`~repro.core.approx.IndexProvenance` registered for
        a route (default route when ``fingerprint`` is None). Routes
        registered without a tag are exact builds by convention."""
        from repro.core.approx import EXACT_PROVENANCE
        fp = fingerprint if fingerprint is not None else self.fingerprint
        if fp not in self._indexes:
            raise KeyError(f"no index registered for fingerprint {fp!r}")
        return self._provenance.get(fp, EXACT_PROVENANCE)

    def fingerprints(self) -> list[str]:
        return list(self._indexes)

    @property
    def index(self) -> Optional[ScanIndex]:
        """Default-route index (single-index back-compat accessor)."""
        pair = self._indexes.get(self.fingerprint)
        return pair[0] if pair else None

    @property
    def g(self) -> Optional[CSRGraph]:
        pair = self._indexes.get(self.fingerprint)
        return pair[1] if pair else None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    async def start(self) -> None:
        if self._task is None:
            # fresh queue per collector: asyncio.Queue binds to the event
            # loop on first use, so an engine reused across a second
            # asyncio.run() must not hand the new collector the old loop's
            # queue (its first get() would die and strand every waiter)
            self._queue = asyncio.Queue()
            self._stopped = False
            self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            # flag first: a request admitted after this point fails fast
            # instead of parking a future behind the stop marker forever
            self._stopped = True
            self._queue.put_nowait(None)
            await self._task
            self._task = None
            # the collector drained on exit; sweep anything that raced in
            # between its last get() and now
            self._reject_pending()
        if self._offload is not None:
            # wait out an in-flight off-loop apply (a torn maintenance job
            # must not outlive the engine it feeds) — but wait *off* the
            # loop: a synchronous shutdown(wait=True) would freeze every
            # other coroutine for the duration of the apply
            offload, self._offload = self._offload, None
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: offload.shutdown(wait=True))

    @property
    def is_running(self) -> bool:
        """Whether the collector task is alive (the engine serves queries
        and may accept maintenance work)."""
        return self._task is not None

    def offload_executor(self) -> ThreadPoolExecutor:
        """Single-worker executor for blocking index-maintenance jobs
        (``LiveIndexService`` runs ``apply_delta`` + delta logging here so
        the collector loop never stalls behind an apply). One worker keeps
        maintenance serial; the loop thread stays free for flushes, which
        is the whole point of taking applies off the event loop."""
        if not self.is_running:
            # stop() shut the previous executor down; lazily resurrecting
            # one here would leak its thread and absorb maintenance into
            # an engine whose collector will never serve the result
            raise RuntimeError(
                "engine is not running: start() it before scheduling "
                "maintenance work")
        if self._offload is None:
            self._offload = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="index-apply")
        return self._offload

    async def run_offloaded(self, fn):
        """Run ``fn()`` in the offload executor and await its result.

        Two things the raw ``loop.run_in_executor`` call site lacked:
        the ``engine.offload_depth`` gauge tracks jobs submitted but not
        finished (the single worker means depth > 1 is a queue — the
        admission-control signal the ROADMAP's fleet work needs), and
        the caller's contextvars are copied into the worker so spans the
        job opens nest under the caller's span (``run_in_executor`` drops
        context on the floor)."""
        depth = self.registry.gauge("engine.offload_depth")
        self.registry.inc("engine.offload_jobs")
        depth.add(1)
        try:
            ctx = contextvars.copy_context()
            return await asyncio.get_running_loop().run_in_executor(
                self.offload_executor(), lambda: ctx.run(fn))
        finally:
            depth.add(-1)

    async def drain(self) -> None:
        """Resolve once every request enqueued *before* this call has been
        flushed. The queue is FIFO and the collector flushes strictly in
        order, so a marker item acts as a barrier — this is what lets a
        hot-swap retire an old index only after all in-flight traffic
        against it has been answered (readers see old or new, never a
        mix, and never a KeyError on a half-retired route)."""
        if self._task is None:
            return
        fut = asyncio.get_running_loop().create_future()
        self._queue.put_nowait((_DRAIN, None, None, fut, time.monotonic()))
        await fut

    async def __aenter__(self) -> "MicroBatchEngine":
        await self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    async def query(self, mu: int, eps: float,
                    fingerprint: Optional[str] = None, *,
                    client: Optional[str] = None,
                    deadline_s: Optional[float] = None) -> ClusterResult:
        """One SCAN query; coalesced with whatever else is in flight.

        ``fingerprint`` selects the target index; ``None`` routes to the
        engine's default (the first registered index). ``client`` is an
        opaque id for per-client admission fairness; ``deadline_s`` lets
        admission reject immediately when the estimated queue wait
        already exceeds the client's patience (both ignored unless the
        engine was configured with ``EngineConfig(admission=...)``; a
        shed raises :class:`~repro.serve.errors.Overloaded`).
        """
        fp = self._admit(fingerprint, client=client, deadline_s=deadline_s)
        if self._task is None:
            await self.start()
        t0 = time.monotonic()
        self.registry.inc("engine.requests")
        mu = int(mu)
        eps_q = quantize_eps(eps, self.cfg.eps_quantum)
        with self.tracer.span("engine.cache_lookup", fingerprint=fp[:12]):
            hit = self.cache.get(fp, mu, eps_q)
        if hit is not None:
            self.registry.inc("engine.cache_hits")
            self.registry.observe("engine.e2e", time.monotonic() - t0)
            return hit
        fut = self._enqueue(fp, _KIND_QUERY, (mu, eps_q), t0)
        try:
            return await fut
        finally:
            # end-to-end latency includes queue wait, batch assembly, and
            # the device call — the number a client actually experiences
            self.registry.observe("engine.e2e", time.monotonic() - t0)

    async def query_seed(self, seed: int, mu: int, eps: float,
                         fingerprint: Optional[str] = None, *,
                         client: Optional[str] = None,
                         deadline_s: Optional[float] = None) -> SeedResult:
        """One seed-set (local) query: the cluster containing ``seed`` at
        (μ, ε) — label, core flag, and full member mask — coalesced with
        other in-flight seed requests into one fixed-shape
        ``query_seeds`` lane batch. Bit-identical to the seed's row of
        the full ``query()`` answer. ``client`` / ``deadline_s`` feed
        admission control exactly as in :meth:`query`."""
        fp = self._admit(fingerprint, client=client, deadline_s=deadline_s)
        index, _ = self._indexes[fp]
        seed = int(seed)
        if not 0 <= seed < index.n:
            raise ValueError(f"seed {seed} out of range for n={index.n}")
        if self._task is None:
            await self.start()
        t0 = time.monotonic()
        self.registry.inc("engine.seed_requests")
        key = (seed, int(mu), quantize_eps(eps, self.cfg.eps_quantum))
        with self.tracer.span("engine.seed_cache_lookup",
                              fingerprint=fp[:12]):
            hit = self.seed_cache.get(fp, *key)
        if hit is not None:
            self.registry.inc("engine.seed_cache_hits")
            self.registry.observe("engine.seed_e2e", time.monotonic() - t0)
            return hit
        fut = self._enqueue(fp, _KIND_SEED, key, t0)
        try:
            return await fut
        finally:
            self.registry.observe("engine.seed_e2e", time.monotonic() - t0)

    def _admit(self, fingerprint: Optional[str], *,
               client: Optional[str] = None,
               deadline_s: Optional[float] = None) -> str:
        """Resolve the route, refuse work on a stopped engine (a request
        enqueued after stop() would otherwise hold a future the dead
        collector never resolves — typed :class:`EngineStopped` so fleet
        retry logic can branch on it), and run admission control when
        configured (a shed raises typed
        :class:`~repro.serve.errors.Overloaded` with ``retry_after``
        instead of silently growing the queue)."""
        fp = fingerprint if fingerprint is not None else self.fingerprint
        if fp not in self._indexes:
            raise KeyError(f"no index registered for fingerprint {fp!r}")
        if self._stopped:
            raise EngineStopped()
        if self.admission is not None:
            self.admission.check(
                client=client, deadline_s=deadline_s,
                queue_depth=self._queue.qsize(),
                offload_depth=self.registry.gauge(
                    "engine.offload_depth").value,
                est_wait_s=self._est_wait_s())
        return fp

    def _est_wait_s(self) -> float:
        """Estimated time-to-service at the current backlog: full flushes
        ahead of a new request × (flush window + observed p50 device
        call). Deliberately a fast, conservative scalar — admission needs
        a shed threshold and a ``retry_after``, not a simulator."""
        flushes_ahead = self._queue.qsize() // max(self.cfg.max_batch, 1) + 1
        per_flush = self.cfg.flush_ms / 1e3
        hist = self.registry.histogram("engine.device_call")
        if hist.count:
            per_flush += hist.quantile(0.5)
        return flushes_ahead * per_flush

    def _enqueue(self, fp: str, kind: str, key, t0: float) -> asyncio.Future:
        # NOTE: callers reach here with no suspension point between
        # their _admit check and this put (start() never actually
        # suspends, and clears _stopped anyway), so an admitted request
        # cannot slip past both stop()'s flag and its _reject_pending()
        fut = asyncio.get_running_loop().create_future()
        self._queue.put_nowait((fp, kind, key, fut, t0))
        self.registry.gauge("engine.queue_depth").set(self._queue.qsize())
        return fut

    # ------------------------------------------------------------------
    # collector loop
    # ------------------------------------------------------------------
    async def _loop(self) -> None:
        while True:
            first = await self._queue.get()
            if first is None:
                self._reject_pending()
                return
            batch = [first]
            t_asm = time.monotonic()
            deadline = asyncio.get_running_loop().time() + self.cfg.flush_ms / 1e3
            while len(batch) < self.cfg.max_batch:
                timeout = deadline - asyncio.get_running_loop().time()
                try:
                    if timeout <= 0:
                        # past the deadline, still take what already
                        # waits: a pause of the loop (a GC pass) must not
                        # leave queued requests to the next device call
                        item = self._queue.get_nowait()
                    else:
                        item = await asyncio.wait_for(self._queue.get(),
                                                      timeout)
                except (asyncio.TimeoutError, asyncio.QueueEmpty):
                    break
                if item is None:
                    self._note_assembly(t_asm, batch)
                    self._flush(batch)
                    self._reject_pending()
                    return
                batch.append(item)
            self._note_assembly(t_asm, batch)
            self._flush(batch)

    def _reject_pending(self) -> None:
        """Collector exit path: drain whatever is still queued and fail
        those futures fast. A request that raced ``stop()`` into the
        queue behind the ``None`` marker would otherwise hold a future
        nobody ever resolves (the old shutdown bug). Drain barriers
        resolve trivially — everything ahead of them has been flushed or
        rejected by the time we get here."""
        rejected = 0
        while True:
            try:
                item = self._queue.get_nowait()
            except asyncio.QueueEmpty:
                break
            if item is None:
                continue
            fut = item[3]
            if fut.done():
                continue
            if item[0] is _DRAIN:
                fut.set_result(None)
                continue
            fut.set_exception(EngineStopped())
            rejected += 1
        if rejected:
            self.registry.inc("engine.rejected_on_stop", rejected)

    def _note_assembly(self, t_asm: float, batch) -> None:
        """Record the size-or-deadline collection window as a span-shaped
        event (first item picked up → flush decision)."""
        self.tracer.event("engine.batch_assembly",
                          time.monotonic() - t_asm, t_start=t_asm,
                          batch=len(batch))

    def _flush(self, batch) -> None:
        """Bucket one collected batch by (fingerprint, kind) and execute
        each bucket as its own device call — seed and global requests
        never share a call (their batch shapes, caches, and compiled
        artifacts differ). A failing bucket rejects only its own
        waiters — sibling buckets and the collector keep running (later
        requests must not hang on a dead loop)."""
        with self.tracer.span("engine.flush", batch=len(batch)):
            now = time.monotonic()
            buckets: dict[tuple, list] = {}
            for item in batch:
                if item[0] is _DRAIN:
                    # barrier marker: everything queued before it is in
                    # this or an earlier (already flushed) batch; real
                    # items in *this* batch flush below, before any
                    # awaiter of the barrier future runs (the loop is
                    # single-threaded). A cancelled waiter (wait_for
                    # timeout) must not kill the collector with
                    # InvalidStateError.
                    if not item[3].done():
                        item[3].set_result(None)
                    continue
                # queue wait = enqueue → flush pickup, per request (the
                # batch deadline shows up here; tail growth means
                # admission trouble)
                wait_name = ("engine.seed_queue_wait"
                             if item[1] == _KIND_SEED
                             else "engine.queue_wait")
                self.tracer.event(wait_name, now - item[4],
                                  t_start=item[4], fingerprint=item[0][:12])
                buckets.setdefault((item[0], item[1]), []).append(item)
            self.registry.gauge("engine.queue_depth").set(
                self._queue.qsize())
            for (fp, kind), bucket in buckets.items():
                try:
                    if kind == _KIND_SEED:
                        self._execute_seeds(fp, bucket)
                    else:
                        self._execute(fp, bucket)
                except Exception as e:  # noqa: BLE001
                    self.registry.inc("engine.bucket_failures")
                    for item in bucket:
                        if not item[3].done():
                            item[3].set_exception(e)

    # ------------------------------------------------------------------
    # per-bucket execution
    # ------------------------------------------------------------------
    def _device_call(self, fp: str, index: ScanIndex, g: CSRGraph,
                     mus, epss):
        if self.cfg.shards is not None and self.cfg.shards > 1:
            from repro.core.distributed import ShardedQueryPlan, query_mesh
            if self._mesh is None:
                self._mesh = query_mesh(self.cfg.shards)
            plan = self._shard_plans.get(fp)
            if plan is None:
                # pad + shard the O(m) operands once per index, not per flush
                plan = self._shard_plans[fp] = ShardedQueryPlan(
                    index, g, self._mesh, registry=self.registry)
            return plan(mus, epss)
        return query_batch(index, g, mus, epss)

    def _execute(self, fp: str, bucket) -> None:
        """One fingerprint's global requests → at most one fixed-shape
        device call."""
        index, g = self._indexes[fp]
        waiters: dict[tuple, list] = {}
        for item in bucket:
            waiters.setdefault(item[2], []).append(item[3])
        self.registry.inc("engine.batches")
        self.registry.inc("engine.deduped", len(bucket) - len(waiters))

        need, resolved = [], {}
        for key in waiters:
            # a twin request may have filled the cache while we queued
            hit = self.cache.peek(fp, *key)
            if hit is not None:
                self.registry.inc("engine.cache_hits")
                resolved[key] = hit
            else:
                need.append(key)

        if need:
            # pad to the fixed slot count: one compiled artifact forever.
            # Padding slots carry the warm-ahead neighborhood of the real
            # requests (already-cached neighbors excluded); any remainder
            # repeats the first real request.
            warm = []
            if self.cfg.warm_ahead:
                warm = self._warm_candidates(fp, need,
                                             self.cfg.max_batch - len(need))
            slots = need + warm
            slots = slots + [need[0]] * (self.cfg.max_batch - len(slots))
            mus = np.asarray([k[0] for k in slots], np.int32)
            epss = np.asarray([k[1] for k in slots], np.float32)
            jit_before = _query_jit_entries()
            lane = self.policy.lane("query")
            self.policy.note("query", lane)
            with self.tracer.span(
                    "engine.device_call", fingerprint=fp[:12],
                    need=len(need), warmed=len(warm), slots=len(slots),
                    shards=self.cfg.shards or 1, lane=lane):
                res = self._device_call(fp, index, g, mus, epss)
                # the span (and the same-named histogram) covers the
                # device's work, then the host copies of its answer
                with self.tracer.span("engine.device_wait"):
                    jax.block_until_ready(res)
                labels = np.asarray(res.labels)
                is_core = np.asarray(res.is_core)
                n_clusters = np.asarray(res.n_clusters)
            jit_delta = _query_jit_entries() - jit_before
            if jit_delta > 0:
                self.registry.inc("engine.jit_recompiles", jit_delta)
            self.registry.inc("engine.device_queries")
            if res.rounds is not None:
                # the batched loop runs until its slowest slot converges
                rounds = np.asarray(res.rounds)
                self.registry.inc("query.rounds", int(rounds.max()))
                self.registry.inc("query.lane_rounds", int(rounds.sum()))
            self.registry.inc("engine.warmed", len(warm))
            for i, key in enumerate(need + warm):
                # copy: row views would pin the whole padded batch array
                # in the cache for as long as the entry lives
                out = ClusterResult(labels=labels[i].copy(),
                                    is_core=is_core[i].copy(),
                                    n_clusters=int(n_clusters[i]))
                self.cache.put(fp, key[0], key[1], out)
                if i < len(need):
                    resolved[key] = out

        for key, futs in waiters.items():
            for fut in futs:
                if not fut.done():
                    fut.set_result(resolved[key])

    def _execute_seeds(self, fp: str, bucket) -> None:
        """One fingerprint's seed requests → fixed-shape ``query_seeds``
        calls of ``seed_batch`` lanes (chunked if a flush carries more
        distinct keys than lanes; each chunk keeps the one batch shape).
        """
        index, g = self._indexes[fp]
        waiters: dict[tuple, list] = {}
        for item in bucket:
            waiters.setdefault(item[2], []).append(item[3])
        self.registry.inc("engine.seed_batches")
        self.registry.inc("engine.seed_deduped", len(bucket) - len(waiters))

        need, resolved = [], {}
        for key in waiters:
            hit = self.seed_cache.peek(fp, *key)
            if hit is not None:
                self.registry.inc("engine.seed_cache_hits")
                resolved[key] = hit
            else:
                need.append(key)

        lanes = self.cfg.seed_batch
        for lo in range(0, len(need), lanes):
            chunk = need[lo:lo + lanes]
            warm = []
            if self.cfg.warm_ahead:
                warm = self._seed_warm_candidates(fp, chunk,
                                                  lanes - len(chunk))
            slots = chunk + warm
            real = len(slots)
            slots = slots + [chunk[0]] * (lanes - real)
            seeds = np.asarray([k[0] for k in slots], np.int32)
            mus = np.asarray([k[1] for k in slots], np.int32)
            epss = np.asarray([k[2] for k in slots], np.float32)
            jit_before = _query_jit_entries()
            q_lane = self.policy.lane("query")
            self.policy.note("query", q_lane)
            with self.tracer.span(
                    "engine.seed_device_call", fingerprint=fp[:12],
                    need=len(chunk), warmed=len(warm), slots=lanes,
                    lane=q_lane):
                res = query_seeds(
                    index, g, seeds, mus, epss,
                    frontier_cap=self.cfg.seed_frontier_cap,
                    window=self.cfg.seed_window,
                    border_cap=self.cfg.seed_border_cap,
                    # spill lanes fall back through the global batch
                    # shape — the artifact the engine already compiles
                    fallback_batch=self.cfg.max_batch)
            jit_delta = _query_jit_entries() - jit_before
            if jit_delta > 0:
                self.registry.inc("engine.jit_recompiles", jit_delta)
            self.registry.inc("engine.seed_device_queries")
            self.registry.inc("engine.seed_warmed", len(warm))
            n_spill = int(np.asarray(res.spilled)[:real].sum())
            if n_spill:
                self.registry.inc("engine.seed_spills", n_spill)
            for i, key in enumerate(chunk + warm):
                out = SeedResult.from_batch_row(res, i, key[0])
                self.seed_cache.put(fp, *key, out)
                if i < len(chunk):
                    resolved[key] = out

        for key, futs in waiters.items():
            for fut in futs:
                if not fut.done():
                    fut.set_result(resolved[key])

    def _seed_warm_candidates(self, fp: str, need, limit: int) -> list:
        """Padding-slot warming for seed lanes: the same seed at its
        (μ±1, ε±δ) neighborhood — parameter-exploring users move on the
        (μ, ε) grid, not across seeds."""
        if limit <= 0:
            return []
        seen = set(need)
        out = []
        for seed, mu, eps_q in need:
            for cmu, ceps in neighborhood(mu, eps_q,
                                          eps_step=self.cfg.warm_eps_step,
                                          quantum=self.cfg.eps_quantum):
                cand = (seed, cmu, ceps)
                if cand in seen:
                    continue
                seen.add(cand)
                if self.seed_cache.peek(fp, *cand) is not None:
                    continue
                out.append(cand)
                if len(out) >= limit:
                    return out
        return out

    def _warm_candidates(self, fp: str, need, limit: int) -> list:
        """Neighborhood settings worth pre-computing in this bucket's
        padding slots: near an actual request, not requested themselves,
        and not already cached."""
        if limit <= 0:
            return []
        seen = set(need)
        out = []
        for mu, eps_q in need:
            for cand in neighborhood(mu, eps_q,
                                     eps_step=self.cfg.warm_eps_step,
                                     quantum=self.cfg.eps_quantum):
                if cand in seen:
                    continue
                seen.add(cand)
                if self.cache.peek(fp, *cand) is not None:
                    continue
                out.append(cand)
                if len(out) >= limit:
                    return out
        return out

    def batch_stats(self) -> dict:
        """Engine + cache counters (for the CLI / bench report)."""
        out = dict(self.stats)
        b = max(out["batches"], 1)
        out["avg_batch"] = (out["requests"] - out["cache_hits"]) / b
        out["indexes"] = len(self._indexes)
        out["approx_indexes"] = sum(
            1 for p in self._provenance.values()
            if getattr(p, "is_approx", False))
        out["jit_recompiles"] = self.registry.counter(
            "engine.jit_recompiles").value
        cache_stats = {f"cache_{k}": v for k, v in self.cache.stats().items()}
        # the engine's own cache_hits (which also counts _execute peek
        # re-checks) must not be clobbered by the store-side hits counter
        cache_stats.pop("cache_hits", None)
        out.update(cache_stats)
        for key in ("seed_requests", "seed_batches", "seed_cache_hits",
                    "seed_deduped", "seed_device_queries", "seed_warmed",
                    "seed_spills", "rejected_on_stop"):
            out[key] = self.registry.counter(f"engine.{key}").value
        out.update({f"seed_cache_{k}": v
                    for k, v in self.seed_cache.stats().items()
                    if k != "hits"})
        return out

    def latency_stats(self, quantiles=(0.5, 0.9, 0.99)) -> dict:
        """Queue-wait / end-to-end latency quantiles in seconds, straight
        from the registry histograms (for the CLI / bench report)."""
        out = {}
        for short, name in (("wait", "engine.queue_wait"),
                            ("e2e", "engine.e2e"),
                            ("seed_wait", "engine.seed_queue_wait"),
                            ("seed_e2e", "engine.seed_e2e")):
            hist = self.registry.histogram(name)
            out[f"{short}_n"] = hist.count
            for q in quantiles:
                out[f"{short}_p{int(q * 100)}"] = hist.quantile(q)
        return out
