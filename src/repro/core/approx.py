"""Approximate-first index construction (paper §5–§6.3) with provenance.

The paper's LSH pass makes index *construction* cheap: sketch every closed
neighborhood once, estimate σ per edge by sketch comparison, and fall back
to exact σ only on edges with a low-degree endpoint (§6.3 degree
heuristic — those route to the small degree-class kernels of the bucketed
similarity engine, so the exact pass never touches a hub-width kernel).
The resulting :class:`~repro.core.index.ScanIndex` is *queryable
immediately* and provably close (Theorems 5.2/5.3), which is what the
approximate-first serve lifecycle exploits: register the sketched index,
answer traffic from it, and refine to the exact index in the background
(:meth:`repro.serve.live.LiveIndexService.register_approximate` /
``refine``).

Because an approximate index is *content-wise* a different artifact from
the exact index of the same graph (its ``edge_sims`` differ, so its
fingerprint differs), every index carries an :class:`IndexProvenance`
tag — exact vs approx, sketch method, sample count, sketch seed — that
flows through the store (persisted as a manifest leaf), the engine router
(queryable per fingerprint), and the CLI. Consumers that care about
guarantees can see *what* they are querying; cache keys stay fingerprint-
based, so approximate and exact answers never alias.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple

import jax

from repro.core.graph import CSRGraph
from repro.core.index import ScanIndex, build_index
from repro.core.lsh import approximate_similarities
from repro.obs.trace import maybe_span

#: methods and the similarity measure each one estimates
_METHOD_MEASURE = {
    "simhash": "cosine",
    "minhash": "jaccard",
    "kpartition": "jaccard",
}


@dataclasses.dataclass(frozen=True)
class ApproxParams:
    """Sketch configuration for one approximate build.

    ``seed`` pins the gaussian projections / permutations, so two builds
    with identical params produce bit-identical sketches, σ̂, and thus
    index fingerprints — which is what lets a restart re-derive the same
    approximate index it persisted.
    """

    method: str = "simhash"       # simhash | minhash | kpartition
    samples: int = 256
    seed: int = 0
    degree_heuristic: bool = True

    def __post_init__(self):
        if self.method not in _METHOD_MEASURE:
            raise ValueError(
                f"unknown LSH method {self.method!r}; "
                f"expected one of {sorted(_METHOD_MEASURE)}")
        if self.samples <= 0:
            raise ValueError(f"samples must be positive, got {self.samples}")

    @property
    def measure(self) -> str:
        """The similarity measure this sketch method estimates."""
        return _METHOD_MEASURE[self.method]

    @classmethod
    def parse(cls, spec: str) -> "ApproxParams":
        """Parse the CLI form ``method[:samples[:seed]]``.

        ``"simhash:256"`` → simhash with 256 samples, seed 0;
        ``"minhash:128:7"`` pins the sketch seed too.
        """
        parts = spec.split(":")
        if not 1 <= len(parts) <= 3 or not parts[0]:
            raise ValueError(
                f"bad approx spec {spec!r}; expected method[:samples[:seed]]")
        method = parts[0]
        try:
            samples = int(parts[1]) if len(parts) > 1 else 256
            seed = int(parts[2]) if len(parts) > 2 else 0
        except ValueError:
            raise ValueError(
                f"bad approx spec {spec!r}; samples/seed must be integers"
            ) from None
        return cls(method=method, samples=samples, seed=seed)

    def spec(self) -> str:
        return f"{self.method}:{self.samples}:{self.seed}"


@dataclasses.dataclass(frozen=True)
class IndexProvenance:
    """How an index's ``edge_sims`` were produced.

    The default-constructed instance (module constant
    :data:`EXACT_PROVENANCE`) names an exact build; approximate builds
    record the full sketch configuration so quality is attributable and
    the build is reproducible.
    """

    kind: str = "exact"                # "exact" | "approx"
    method: Optional[str] = None
    samples: int = 0
    seed: int = 0
    degree_heuristic: bool = True

    @property
    def is_approx(self) -> bool:
        return self.kind == "approx"

    def describe(self) -> str:
        if not self.is_approx:
            return "exact"
        dh = "+degree-heuristic" if self.degree_heuristic else ""
        return f"approx({self.method}, k={self.samples}, seed={self.seed}{dh})"

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, payload: str) -> "IndexProvenance":
        data = json.loads(payload)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in data.items() if k in known})

    @classmethod
    def for_params(cls, params: ApproxParams) -> "IndexProvenance":
        return cls(kind="approx", method=params.method,
                   samples=params.samples, seed=params.seed,
                   degree_heuristic=params.degree_heuristic)


EXACT_PROVENANCE = IndexProvenance()


class ApproxIndexBuilder:
    """Build a queryable :class:`ScanIndex` from LSH-sketched similarities.

    ``measure`` must match what ``params.method`` estimates (simhash →
    cosine, minhash/kpartition → jaccard) — a mismatch is a config error,
    caught at construction, not a silently wrong index.
    """

    def __init__(self, measure: str = "cosine",
                 params: ApproxParams = ApproxParams(), *, policy=None):
        if params.measure != measure:
            raise ValueError(
                f"method {params.method!r} estimates {params.measure!r} "
                f"similarity, not {measure!r}")
        self.measure = measure
        self.params = params
        # execution policy for the sketch-comparison / exact-pass lanes
        # (None → the process default); lane choice never moves σ̂ bits
        self.policy = policy

    @property
    def provenance(self) -> IndexProvenance:
        return IndexProvenance.for_params(self.params)

    def similarities(self, g: CSRGraph, *, tracer=None) -> jax.Array:
        """The sketched per-half-edge σ̂ (exact on §6.3 heuristic edges);
        ``tracer`` traces the pass (:func:`approximate_similarities`)."""
        p = self.params
        return approximate_similarities(
            g, measure=self.measure, method=p.method, samples=p.samples,
            key=jax.random.PRNGKey(p.seed),
            degree_heuristic=p.degree_heuristic, policy=self.policy,
            tracer=tracer)

    def build(self, g: CSRGraph, *,
              tracer=None) -> Tuple[ScanIndex, IndexProvenance]:
        """→ (approximate index, its provenance tag).

        ``tracer`` (a :class:`repro.obs.Tracer`) wraps the construction in
        an ``index.approx_build`` span so approximate-build latency lands
        in the same histogram taxonomy as the rest of the serve stack;
        inside it the ``lsh.*`` spans of the sketched pass and the
        ``index.build`` of the orders. Without one nothing is timed.
        """
        p = self.params
        with maybe_span(tracer, "index.approx_build", method=p.method,
                        samples=p.samples, seed=p.seed, n=g.n, m=g.m):
            index = build_index(g, self.measure,
                                sims=self.similarities(g, tracer=tracer),
                                tracer=tracer)
        return index, self.provenance


def build_approx_index(
    g: CSRGraph,
    *,
    measure: str = "cosine",
    method: str = "simhash",
    samples: int = 256,
    seed: int = 0,
    degree_heuristic: bool = True,
) -> Tuple[ScanIndex, IndexProvenance]:
    """One-shot convenience wrapper over :class:`ApproxIndexBuilder`."""
    params = ApproxParams(method=method, samples=samples, seed=seed,
                          degree_heuristic=degree_heuristic)
    return ApproxIndexBuilder(measure, params).build(g)
