"""Calibrated dispatch shapes: the :class:`AutotuneProfile`.

Every knob the execution policy consults when running a hot op — the hub
storage tile and the per-kernel block shapes — lives in one frozen
dataclass. The **default instance is bit-for-bit today's constants**
(``HUB_TILE=2048``, gram/simhash blocks 128, probe blocks 256, hamming
block 1024): an old checkpoint without a persisted profile, or a policy
that never autotuned, behaves exactly like the code did before this
subsystem existed.

Profiles persist as a versioned JSON manifest leaf next to the index
(``repro.serve.store.IndexStore``), so a served index remembers the
shapes it was tuned with; :func:`autotune` produces a fresh profile
from a one-shot microbenchmark sweep under a ``backend.autotune`` span.

The profile only moves *shapes* (padding, tiling, chunking), never math:
the bit-identity contract (unweighted σ bit-for-bit, weighted to ULP)
holds under any profile, which is what makes retuning safe.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

PROFILE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class AutotuneProfile:
    """Dispatch shapes for one platform. Defaults = legacy constants."""

    version: int = PROFILE_VERSION
    platform: str = "default"     # backend the sweep ran on ("default" = untuned)
    # -- similarity-plan shape (core.similarity) ---------------------------
    hub_tile: int = 2048          # storage tile width for hub rows
    # -- kernel block shapes ------------------------------------------------
    gram_block: int = 128         # masked_gram bm/bn/bk (triangle_count op)
    probe_be: int = 256           # bucket_probe edge-block
    probe_bt: int = 256           # bucket_probe target-tile stream width
    simhash_block: int = 128      # simhash_pack bm/bk (all k samples per block)
    hamming_block: int = 1024     # hamming_distance edge-block
    # interpret-mode grids unroll at trace time, so the interpret lane caps
    # similarity chunks to keep compile time bounded (compiled lane ignores)
    probe_interpret_chunk: int = 512

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, payload: str) -> "AutotuneProfile":
        """The profile a stored payload describes; keys this version no
        longer has (such as a retired dispatch cutoff) are dropped."""
        data = json.loads(payload)
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in data.items() if k in names})


DEFAULT_PROFILE = AutotuneProfile()

# candidate grids for the one-shot sweep; single-valued entries are taken
# without timing (tests shrink these to keep the sweep cheap)
DEFAULT_CANDIDATES = {
    "gram_block": (64, 128),
    "probe_block": ((128, 128), (256, 256)),   # (be, bt) pairs
    "hamming_block": (512, 1024),
    "simhash_block": (128,),                   # bs must stay 128-aligned
    "hub_tile": (2048,),                       # plan rebuild too costly to sweep
}


def _median_seconds(fn, trials: int) -> float:
    import time

    import jax

    fn()                                       # warmup (compile)
    times = []
    for _ in range(max(trials, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def autotune(policy=None, *, candidates: Optional[dict] = None,
             trials: int = 1) -> AutotuneProfile:
    """One-shot microbenchmark sweep → a fresh :class:`AutotuneProfile`.

    Times each candidate block shape on small synthetic operands through
    the lane the given policy would actually dispatch (its kernel lane on
    this platform), picks the argmin per knob, and stamps the platform.
    Runs under a ``backend.autotune`` span on the policy's registry.
    Single-valued candidate grids skip timing entirely.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.backend.policy import LANE_REF, default_policy
    from repro.obs import Tracer

    pol = policy if policy is not None else default_policy()
    cand = dict(DEFAULT_CANDIDATES)
    cand.update(candidates or {})
    tracer = Tracer(pol.registry)

    chosen = dataclasses.asdict(pol.profile)
    chosen["platform"] = pol.platform()
    chosen["version"] = PROFILE_VERSION

    with tracer.span("backend.autotune", platform=chosen["platform"],
                     trials=trials):
        lane = pol.kernel_lane("bucket_probe")
        interpret = lane != "pallas-compiled"
        timed = 0

        if len(cand["gram_block"]) > 1 and lane != LANE_REF:
            from repro.kernels.triangle_count import masked_gram
            n = 256
            w = jnp.asarray(np.random.default_rng(0).standard_normal(
                (n, n)), jnp.float32)
            mask = jnp.ones((n, n), jnp.float32)
            best = min(
                cand["gram_block"],
                key=lambda b: _median_seconds(
                    lambda: masked_gram(w, mask, bm=b, bn=b, bk=b,
                                        interpret=interpret), trials))
            chosen["gram_block"] = int(best)
            timed += len(cand["gram_block"])

        if len(cand["probe_block"]) > 1 and lane != LANE_REF:
            from repro.kernels.bucket_probe import bucket_probe
            rng = np.random.default_rng(1)
            e, p, t = 256, 64, 256
            ids_p = jnp.asarray(rng.integers(0, 1 << 20, (e, p)), jnp.int32)
            ids_t = jnp.asarray(rng.integers(0, 1 << 20, (e, t)), jnp.int32)
            w_p = jnp.ones((e, p), jnp.float32)
            w_t = jnp.ones((e, t), jnp.float32)
            best = min(
                cand["probe_block"],
                key=lambda bb: _median_seconds(
                    lambda: bucket_probe(ids_p, w_p, ids_t, w_t,
                                         be=min(bb[0], e), bt=min(bb[1], t),
                                         interpret=interpret), trials))
            chosen["probe_be"], chosen["probe_bt"] = int(best[0]), int(best[1])
            timed += len(cand["probe_block"])

        if len(cand["hamming_block"]) > 1 and lane != LANE_REF:
            from repro.kernels.hamming import hamming_distance
            rng = np.random.default_rng(2)
            e, words = 2048, 8
            sk = jnp.asarray(
                rng.integers(0, 1 << 32, (2, e, words), dtype=np.uint64)
                .astype(np.uint32))
            best = min(
                cand["hamming_block"],
                key=lambda b: _median_seconds(
                    lambda: hamming_distance(sk[0], sk[1], be=min(b, e),
                                             interpret=interpret), trials))
            chosen["hamming_block"] = int(best)
            timed += len(cand["hamming_block"])

        if len(cand["simhash_block"]) == 1:
            chosen["simhash_block"] = int(cand["simhash_block"][0])
        if len(cand["hub_tile"]) == 1:
            chosen["hub_tile"] = int(cand["hub_tile"][0])

        if pol.registry is not None:
            pol.registry.inc("backend.autotune_runs")
            pol.registry.inc("backend.autotune_candidates_timed", timed)

    return AutotuneProfile(**chosen)
