"""What every cell of the SCAN-service benchmark shares: finding a cell's
files by name, the run's graph, the service, the clock of compilations,
and the comparison of what the timed path produced with the reference.

Nothing here knows a particular configuration, traffic mix or per-layer
metric: those are files under ``bench/configs``, ``bench/traffic`` and
``bench/layers`` that :func:`find` locates by the names in
``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import threading
from pathlib import Path

import numpy as np

from bench import reference

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"          # run-time files only (gitignored)
CATALOG = OUT / "catalog"          # the service's snapshots, cleared per run
TRACES = OUT / "trace"             # profiler output, cleared per run
COMPILE_CACHE = ROOT / ".jax_cache"

KINDS = {"configs": ".json", "traffic": ".json", "layers": ".py",
         "drivers": ".py", "generators": ".py"}


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown cell, ...)."""


def find(kind: str, name: str, base: Path = BENCH) -> Path:
    """The file of ``kind`` named ``name`` (``configs/<name>.json``, ...),
    looked for under ``base`` and then under the benchmark's own tree."""
    for root in dict.fromkeys((Path(base), BENCH)):
        path = root / kind / f"{name}{KINDS[kind]}"
        if path.is_file():
            return path
    raise BenchError(f"no {kind} file for {name!r} under {base}")


def load_json(kind: str, name: str, base: Path = BENCH) -> dict:
    with open(find(kind, name, base)) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: Path = BENCH):
    """Import ``<kind>/<name>.py`` by path (names may hold dots)."""
    path = find(kind, name, base)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, all from the run's seed."""
    words = [seed % (1 << 32), (seed >> 32) % (1 << 32)]
    words += [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


class CompileClock:
    """Backend compiles (count, seconds) seen by this process, from JAX's
    monitoring events; a persistent-cache hit is not a backend compile."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


# ---------------------------------------------------------------------------
# the run's graph
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunGraph:
    """The configuration's graph under this run's vertex names.

    ``base`` is the generator's raw endpoint list at the configuration's
    fixed ``graph_seed``; every run renames its vertices by ``perm``
    (drawn from ``--seed``), so all seeds run the same graph in another
    order."""

    n: int
    base: np.ndarray      # int64[E, 2] raw pairs, base vertex names
    perm: np.ndarray      # int64[n]: base name → run name

    def edges(self, perm: np.ndarray | None = None,
              rng: np.random.Generator | None = None) -> np.ndarray:
        """Raw pairs under ``perm`` (default the run's), rows shuffled."""
        e = (self.perm if perm is None else perm)[self.base]
        return e[rng.permutation(len(e))] if rng is not None else e

    def reference(self) -> reference.Graph:
        """The canonical base graph with its triangle counts (host)."""
        return reference.Graph.from_pairs(self.n, self.base).with_triangles()


def graph_params(config: dict, rehearse: bool) -> dict:
    params = {k: config[k] for k in config["generator_params"]}
    if rehearse:
        params.update(config["rehearsal"])
    return params


def make_graph(config: dict, seed: int, rehearse: bool = False) -> RunGraph:
    gen = load_module("generators", config["generator"])
    n, base = gen.edges(**graph_params(config, rehearse),
                        seed=config["graph_seed"])
    perm = rng_for(seed, "perm").permutation(n)
    return RunGraph(n=n, base=base, perm=perm)


def fresh_dir(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


# ---------------------------------------------------------------------------
# comparison with the reference
# ---------------------------------------------------------------------------
INDEX_FIELDS = ("offsets_c", "no_nbrs", "no_sims", "no_self", "cdeg",
                "co_vertex", "co_theta", "co_offsets")


def host_index(index) -> dict:
    """The served index's arrays on the host (before the program's state
    is freed)."""
    out = {k: np.asarray(getattr(index, k)) for k in INDEX_FIELDS}
    out["edge_sims"] = np.asarray(index.edge_sims)
    return out


def host_graph(g) -> dict:
    return {"offsets": np.asarray(g.offsets), "nbrs": np.asarray(g.nbrs)}


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a)
    if a.dtype == np.float32:
        return a.view(np.uint32)
    return a.astype(np.int64)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Entries that differ bit for bit; a length mismatch counts every
    entry of the longer array."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size)
    return int((_bits(got) != _bits(want)).sum())


@dataclasses.dataclass
class RefIndex:
    """The reference's graph, σ and orders for one index version."""

    offsets: np.ndarray
    edge_u: np.ndarray
    nbrs: np.ndarray
    sims: np.ndarray
    orders: dict

    @staticmethod
    def build(graph: reference.Graph, dtype: str = "float32") -> "RefIndex":
        offsets, eu, nb, tri = graph.csr()
        sims = reference.sigma(eu, nb, tri, offsets, dtype)
        return RefIndex(offsets, eu, nb, sims,
                        reference.orders(offsets, eu, nb, sims))

    def cluster(self, mu: int, eps: float):
        return reference.cluster(len(self.offsets) - 1, self.edge_u,
                                 self.nbrs, self.sims, mu, eps)


def compare_index(got_index: dict, got_graph: dict, ref: RefIndex) -> dict:
    """Mismatch counts of one served index against the reference:
    ``graph_bad`` (CSR), ``sigma_bad`` (σ per half-edge, bit for bit) and
    ``order_bad`` (NO and CO slots)."""
    graph_bad = (mismatches(got_graph["offsets"], ref.offsets)
                 + mismatches(got_graph["nbrs"], ref.nbrs))
    sigma_bad = mismatches(got_index["edge_sims"], ref.sims)
    order_bad = sum(mismatches(got_index[k], ref.orders[k])
                    for k in INDEX_FIELDS)
    return {"graph_bad": graph_bad, "sigma_bad": sigma_bad,
            "order_bad": order_bad}


class Clusterings:
    """Reference answers per (μ, ε), computed once each."""

    def __init__(self, ref: RefIndex):
        self.ref = ref
        self._memo: dict = {}

    def __call__(self, mu: int, eps: float):
        key = (int(mu), float(np.float32(eps)))
        if key not in self._memo:
            self._memo[key] = self.ref.cluster(*key)
        return self._memo[key]


def global_bad(answer, want) -> int:
    """Vertices whose label or core bit differ in one global answer."""
    labels, core = want
    got_l, got_c = np.asarray(answer.labels), np.asarray(answer.is_core)
    if got_l.shape != labels.shape or got_c.shape != core.shape:
        return len(labels)
    return int((got_l != labels).sum() + (got_c != core).sum())


# ---------------------------------------------------------------------------
# statistics of a window
# ---------------------------------------------------------------------------
def eps_of(units: int, grid: float) -> float:
    """ε of a grid point, as the drivers send it."""
    return round(units * grid, 10)


def latency_stats(lat: np.ndarray) -> dict:
    """Exact statistics of per-request latencies in seconds (numpy's
    percentiles over every sample, not a histogram's buckets)."""
    lat = np.asarray(lat, np.float64)
    if not len(lat):
        return {"n": 0}
    return {"n": int(len(lat)), "p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "p99": float(np.percentile(lat, 99)),
            "mean": float(lat.mean()), "max": float(lat.max())}


def fmt_stats(stats: dict) -> str:
    return " ".join(f"{k}={v:.6g}" for k, v in stats.items())


def report(traffic: dict, stats: dict) -> dict:
    """The end-to-end metrics a traffic file publishes: ``report`` maps
    a metric name to a statistic of the window and a scale."""
    out = {}
    for name, how in traffic["report"].items():
        if how["stat"] in stats:
            out[name] = stats[how["stat"]] * how.get("scale", 1.0)
    return out
