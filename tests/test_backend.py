"""Execution-policy backend: lane-matrix oracle, per-call dispatch, and
the import-time-freeze regression.

The contract under test: every lane of every hot op (``ref`` pure-jnp /
``pallas-interpret`` / ``pallas-compiled``) reproduces the ``ref`` lane
bit-for-bit on unweighted σ and to ULP on weighted σ, so lane choice can
never move an index fingerprint; lane resolution (platform, ``REPRO_LANE``)
happens per call, never at import.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import padding
from repro.backend.policy import (LANE_COMPILED, LANE_INTERPRET, LANE_REF,
                                  OPS, ExecutionPolicy, default_policy)
from repro.backend.profile import (DEFAULT_PROFILE, PROFILE_VERSION,
                                   AutotuneProfile, autotune)
from repro.backend.padding import np_pow2ceil
from repro.core import (compute_similarities, plan_for, power_law_graph,
                        random_graph)
from repro.core import similarity as sim_mod
from repro.kernels import ops
from repro.obs import MetricsRegistry

RNG = np.random.default_rng(0)

# on CPU the compiled lane cannot run; the matrix covers what can
_HOST_LANES = [LANE_REF, LANE_INTERPRET]


# ---------------------------------------------------------------------------
# lane-matrix oracle: every lane of every hot op vs the ref lane
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lane", _HOST_LANES)
@pytest.mark.parametrize("measure", ["cosine", "jaccard"])
def test_lane_matrix_gram(lane, measure):
    g = random_graph(150, 6.0, seed=1)
    want = np.asarray(ops.edge_similarities_gram(g, measure, lane=LANE_REF))
    got = np.asarray(ops.edge_similarities_gram(g, measure, lane=lane))
    # unweighted graph: integer-valued dots in f32 → bit-for-bit
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("lane", _HOST_LANES)
@pytest.mark.parametrize("weighted", [False, True])
def test_lane_matrix_bucket_probe(lane, weighted):
    e, p, t, n = 64, 8, 48, 64
    ids_p = np.sort(RNG.choice(n, size=(e, p), replace=True), axis=1)
    ids_t = np.sort(RNG.choice(n, size=(e, t), replace=True), axis=1)
    if weighted:
        w_p = RNG.uniform(0.1, 1.0, size=(e, p)).astype(np.float32)
        w_t = RNG.uniform(0.1, 1.0, size=(e, t)).astype(np.float32)
    else:
        w_p = np.ones((e, p), np.float32)
        w_t = np.ones((e, t), np.float32)
    args = (jnp.asarray(ids_p, jnp.int32), jnp.asarray(w_p),
            jnp.asarray(ids_t, jnp.int32), jnp.asarray(w_t), n)
    want_dot, want_cnt = ops.bucket_probe_stats(*args, lane=LANE_REF)
    dot, cnt = ops.bucket_probe_stats(*args, lane=lane)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(want_cnt))
    if weighted:
        np.testing.assert_allclose(np.asarray(dot), np.asarray(want_dot),
                                   rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(dot), np.asarray(want_dot))


@pytest.mark.parametrize("lane", _HOST_LANES)
def test_lane_matrix_simhash_and_hamming(lane):
    g = random_graph(130, 5.0, seed=2)
    k = 96
    key = jax.random.PRNGKey(0)
    want_sk = np.asarray(ops.simhash_sketches_kernel(g, k, key,
                                                     lane=LANE_REF))
    sk = np.asarray(ops.simhash_sketches_kernel(g, k, key, lane=lane))
    np.testing.assert_array_equal(sk, want_sk)  # packed bits: exact
    want = np.asarray(ops.simhash_edge_similarity_kernel(
        jnp.asarray(sk), g.edge_u, g.nbrs, k, lane=LANE_REF))
    got = np.asarray(ops.simhash_edge_similarity_kernel(
        jnp.asarray(sk), g.edge_u, g.nbrs, k, lane=lane))
    # XOR/popcount is integer-exact; the cos epilogue is the same
    # elementwise expression → bit-for-bit
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("weighted", [False, True])
def test_full_similarity_pass_lane_identity(monkeypatch, weighted):
    """The whole σ engine (plan → groups → epilogue) under a forced
    Pallas-interpret lane reproduces the default jnp engine — bit-for-bit
    on unweighted graphs, ULP-close on weighted."""
    g = random_graph(300, 8.0, seed=3, weighted=weighted)
    monkeypatch.delenv("REPRO_LANE", raising=False)
    want = np.asarray(compute_similarities(g, "cosine"))
    monkeypatch.setenv("REPRO_LANE", LANE_INTERPRET)
    got = np.asarray(compute_similarities(g, "cosine"))
    if weighted:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)


def _group_widths(plan, eu, ev):
    """(probe width, target width) of each half-edge's σ group."""
    pu, pv, _ = plan.route(eu, ev)
    widths = np.asarray(plan.widths)
    return (np_pow2ceil(plan.vtiles[pu]) * widths[plan.vclass[pu]],
            np_pow2ceil(plan.vtiles[pv]) * widths[plan.vclass[pv]])


# a power-law graph whose narrow probe rows (8–128 wide) meet targets from
# their own width up to a two-tile hub (degree 2,297 > HUB_TILE: 4,096 wide)
_NARROW = [(8, 8), (16, 32), (32, 64), (64, 128), (128, 256), (8, 4096),
           (128, 4096)]


@pytest.mark.parametrize("weighted", [False, True])
def test_narrow_groups_pallas_lane_matches_ref(monkeypatch, weighted):
    """``half_edge_sims`` under the Pallas lane reproduces the ref lane on
    groups whose probe rows are 8–128 wide, one of them into a multi-tile
    hub: bit for bit unweighted, to ULP weighted. Every half-edge the
    Pallas pass computed counts under ``similarity.sigma_kernel``, none
    of the ref pass's."""
    from repro.obs import Tracer

    monkeypatch.delenv("REPRO_LANE", raising=False)
    g = power_law_graph(2500, avg_degree=6.0, hub_degree=2200, seed=5,
                        weighted=weighted)
    plan = plan_for(g)
    eu, ev = np.asarray(g.edge_u, np.int64), np.asarray(g.nbrs, np.int64)
    pe, te = _group_widths(plan, eu, ev)
    # twins share their group, so a set of whole groups is twin-closed
    sel = np.flatnonzero(np.isin(pe * (1 << 20) + te,
                                 [p * (1 << 20) + t for p, t in _NARROW]))
    runs = {}
    for lane in (LANE_REF, LANE_INTERPRET):
        tr = Tracer(MetricsRegistry())
        sims = plan.half_edge_sims(
            g, sel, policy=ExecutionPolicy(forced_lane=lane), tracer=tr)
        runs[lane] = (np.asarray(sims), tr.registry.snapshot()["counters"])
    assert {(p, t) for p, t, _, _ in plan.last_group_shapes} == set(_NARROW)
    want, got = runs[LANE_REF][0], runs[LANE_INTERPRET][0]
    if weighted:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))
    for lane, kernel in ((LANE_REF, 0), (LANE_INTERPRET, len(sel) // 2)):
        counters = runs[lane][1]
        assert counters["similarity.sigma_computed"] == len(sel) // 2
        assert counters["similarity.sigma_kernel"] == kernel


@pytest.mark.parametrize("platform,forced,want", [
    ("tpu", None, LANE_COMPILED),
    ("cpu", None, LANE_REF),
    ("tpu", LANE_REF, LANE_REF),
    ("cpu", LANE_INTERPRET, LANE_INTERPRET),
])
def test_sigma_groups_of_every_width_take_the_platform_lane(
        monkeypatch, platform, forced, want):
    """The σ pass's lane depends on the platform alone: on a (mocked) TPU
    every group, from 8-wide probe rows to hub tiles, runs the compiled
    kernel; elsewhere the ref lane; a forced lane still wins. The chunk
    programs are stubbed, so nothing runs on the mocked chip."""
    from unittest import mock

    if forced is None:
        monkeypatch.delenv("REPRO_LANE", raising=False)
    else:
        monkeypatch.setenv("REPRO_LANE", forced)
    seen = set()

    def stub(lane):
        def chunk(edges, *operands, **static):
            assert static.get("interpret", True) == (lane != LANE_COMPILED)
            seen.add(lane)
            return jax.numpy.zeros(edges.shape[1], jax.numpy.float32)
        return chunk

    monkeypatch.setattr(sim_mod, "_bucket_sims_chunk", stub(LANE_REF))
    monkeypatch.setattr(sim_mod, "_bucket_sims_chunk_pallas",
                        stub(LANE_COMPILED if platform == "tpu"
                             else LANE_INTERPRET))
    g = power_law_graph(2500, avg_degree=6.0, hub_degree=2200, seed=5)
    plan = plan_for(g)
    with mock.patch("jax.default_backend", return_value=platform):
        plan.edge_sims(g.edge_u, g.nbrs, g.wgts)
    shapes = plan.last_group_shapes
    assert {pe for pe, _, _, _ in shapes} >= {8, 16, 32, 64, 128, 256}
    assert max(te for _, te, _, _ in shapes) == 4096
    assert {lane for _, _, lane, _ in shapes} == {want} == seen


# ---------------------------------------------------------------------------
# per-call resolution: REPRO_LANE, clamping, platform
# ---------------------------------------------------------------------------
def test_env_lane_read_per_call(monkeypatch):
    pol = ExecutionPolicy()
    monkeypatch.delenv("REPRO_LANE", raising=False)
    assert pol.forced_lane() is None
    # the same policy object changes its answer when the env changes —
    # nothing is frozen at construction
    monkeypatch.setenv("REPRO_LANE", LANE_REF)
    assert pol.lane("bucket_probe") == LANE_REF
    monkeypatch.setenv("REPRO_LANE", LANE_INTERPRET)
    assert pol.lane("bucket_probe") == LANE_INTERPRET
    assert pol.kernel_lane("hamming") == LANE_INTERPRET
    monkeypatch.setenv("REPRO_LANE", "not-a-lane")
    with pytest.raises(ValueError, match="unknown lane"):
        pol.lane("bucket_probe")


def test_forced_lane_clamps_to_registered_lanes(monkeypatch):
    """Ops with only a ref lane stay on it under any forced lane — the
    (μ, ε) query path honestly reports ref, never pretends."""
    monkeypatch.setenv("REPRO_LANE", LANE_COMPILED)
    pol = ExecutionPolicy()
    assert OPS["query"] == (LANE_REF,)
    assert pol.lane("query") == LANE_REF
    assert pol.kernel_lane("query") == LANE_REF


@pytest.mark.parametrize("via", ["env", "constructor"])
def test_forced_interpret_lane_refused_on_tpu(monkeypatch, via):
    """On a TPU a forced pallas-interpret lane would emulate every kernel
    on the host while the chip idles: the policy raises instead."""
    from unittest import mock
    if via == "env":
        monkeypatch.setenv("REPRO_LANE", LANE_INTERPRET)
        pol = ExecutionPolicy()
    else:
        monkeypatch.delenv("REPRO_LANE", raising=False)
        pol = ExecutionPolicy(forced_lane=LANE_INTERPRET)
    with mock.patch("jax.default_backend", return_value="tpu"):
        for resolve in (lambda: pol.lane("bucket_probe"),
                        lambda: pol.kernel_lane("hamming"), pol.describe):
            with pytest.raises(RuntimeError, match="pallas-interpret"):
                resolve()
        if via == "constructor":
            # the other lanes stay selectable on the chip
            assert ExecutionPolicy(forced_lane=LANE_REF).lane(
                "bucket_probe") == LANE_REF
    # off the chip the same policy keeps its test lane
    with mock.patch("jax.default_backend", return_value="cpu"):
        assert pol.lane("bucket_probe") == LANE_INTERPRET


def test_compile_cache_dir_placed_from_outside_or_fixed(monkeypatch,
                                                        tmp_path):
    from repro.backend import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        outside = str(tmp_path / "cache")
        monkeypatch.setenv(cc.ENV_DIR, outside)
        jax.config.update("jax_compilation_cache_dir", None)
        assert cc.enable_compile_cache() == outside
        # JAX reads the variable itself; the helper sets no other path
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv(cc.ENV_DIR)
        first = cc.enable_compile_cache()
        assert first == cc.compile_cache_dir() == cc.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_constructor_lane_beaten_by_env(monkeypatch):
    monkeypatch.delenv("REPRO_LANE", raising=False)
    pol = ExecutionPolicy(forced_lane=LANE_REF)
    assert pol.lane("bucket_probe") == LANE_REF
    monkeypatch.setenv("REPRO_LANE", LANE_INTERPRET)
    assert pol.lane("bucket_probe") == LANE_INTERPRET


def test_lane_counters_flow(monkeypatch):
    monkeypatch.delenv("REPRO_LANE", raising=False)
    reg = MetricsRegistry()
    pol = ExecutionPolicy(forced_lane=LANE_INTERPRET, registry=reg)
    g = random_graph(120, 5.0, seed=4)
    ops.edge_similarities_gram(g, "cosine", policy=pol)
    ops.simhash_sketches_kernel(g, 64, jax.random.PRNGKey(0), policy=pol)
    snap = reg.snapshot()["counters"]
    assert snap[f"backend.lane.triangle_count.{LANE_INTERPRET}"] == 1
    assert snap[f"backend.lane.simhash.{LANE_INTERPRET}"] == 1


def test_describe_block(monkeypatch):
    monkeypatch.delenv("REPRO_LANE", raising=False)
    desc = ExecutionPolicy(forced_lane=LANE_REF).describe()
    assert desc["forced_lane"] == LANE_REF
    assert desc["platform"] == jax.default_backend()
    assert set(desc["lanes"]) == set(OPS)
    assert desc["profile"]["hub_tile"] == DEFAULT_PROFILE.hub_tile


def test_no_import_time_backend_freeze():
    """Importing the kernel wrappers must neither initialize a jax backend
    nor freeze the platform decision — the regression that motivated this
    subsystem (`_ON_TPU`/`_INTERPRET` module constants captured at import,
    so `JAX_PLATFORMS` set afterwards was silently ignored)."""
    code = """
import repro.kernels.ops, repro.core.similarity
from jax._src import xla_bridge as xb
assert not xb._backends, "importing kernel wrappers initialized jax"

from unittest import mock
from repro.backend.policy import (LANE_COMPILED, LANE_INTERPRET, LANE_REF,
                                  ExecutionPolicy)
pol = ExecutionPolicy()
with mock.patch("jax.default_backend", return_value="tpu"):
    assert pol.kernel_lane("bucket_probe") == LANE_COMPILED
    assert pol.lane("bucket_probe") == LANE_COMPILED
with mock.patch("jax.default_backend", return_value="cpu"):
    assert pol.kernel_lane("bucket_probe") == LANE_INTERPRET
    assert pol.lane("bucket_probe") == LANE_REF
print("OK")
"""
    env = dict(os.environ)
    env.pop("REPRO_LANE", None)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_no_module_level_backend_constant():
    """No module may capture platform state at import again."""
    import inspect

    import repro.core.similarity as sim_mod
    src = inspect.getsource(ops) + inspect.getsource(sim_mod)
    for frozen in ("_ON_TPU", "_INTERPRET ="):
        assert frozen not in src


# ---------------------------------------------------------------------------
# padding helpers (deterministic; the hypothesis property lives in
# test_backend_property.py)
# ---------------------------------------------------------------------------
def test_padding_helpers():
    assert [padding.pow2ceil(x) for x in (1, 2, 3, 5, 8, 1000)] == \
        [1, 2, 4, 8, 8, 1024]
    assert padding.pow2ceil(0, floor=8) == 8
    assert padding.pow2_bucket(100, floor=64) == 128
    assert padding.pow2_bucket(64, floor=64) == 64
    np.testing.assert_array_equal(
        padding.np_pow2ceil(np.array([1, 3, 4, 9])), [1, 4, 4, 16])
    np.testing.assert_array_equal(
        padding.np_log2(np.array([1, 2, 8, 1024])), [0, 1, 3, 10])
    a = padding.pad1(np.arange(3, dtype=np.int32), 2, -1)
    np.testing.assert_array_equal(a, [0, 1, 2, -1, -1])
    x = padding.pad_to(jnp.ones((3, 5)), 4, (0, 1))
    assert x.shape == (4, 8)
    assert float(x.sum()) == 15.0


def test_similarity_reexports_padding_helpers():
    """core.similarity keeps the old underscore names as aliases of the
    shared module — one definition, not two."""
    import repro.core.similarity as sim_mod
    assert sim_mod._pow2ceil is padding.pow2ceil
    assert sim_mod._pow2_bucket is padding.pow2_bucket
    assert sim_mod._pad1 is padding.pad1


# ---------------------------------------------------------------------------
# autotune: profile round-trip, observability, default behavior
# ---------------------------------------------------------------------------
def test_profile_json_roundtrip():
    prof = AutotuneProfile(platform="cpu", gram_block=64, probe_be=128)
    back = AutotuneProfile.from_json(prof.to_json())
    assert back == prof
    # unknown keys from a future profile version are ignored, not fatal
    import json
    payload = json.loads(prof.to_json())
    payload["some_future_knob"] = 7
    assert AutotuneProfile.from_json(json.dumps(payload)) == prof


def test_default_profile_is_legacy_constants():
    from repro.core import similarity as sim_mod
    assert DEFAULT_PROFILE.hub_tile == sim_mod.HUB_TILE == 2048
    assert DEFAULT_PROFILE.version == PROFILE_VERSION
    assert DEFAULT_PROFILE.platform == "default"


def test_autotune_produces_profile_under_span(monkeypatch):
    monkeypatch.delenv("REPRO_LANE", raising=False)
    reg = MetricsRegistry()
    pol = ExecutionPolicy(registry=reg)
    # two-candidate hamming grid: cheap to time in interpret mode; the
    # rest single-valued (taken without timing)
    prof = autotune(pol, candidates={
        "gram_block": (128,), "probe_block": ((256, 256),),
        "hamming_block": (512, 1024), "simhash_block": (128,),
        "hub_tile": (2048,)}, trials=1)
    assert prof.platform == jax.default_backend()
    assert prof.hamming_block in (512, 1024)
    assert reg.histogram("backend.autotune").count == 1
    assert reg.counter("backend.autotune_runs").value == 1
    assert reg.counter("backend.autotune_candidates_timed").value == 2


def test_autotune_ref_lane_skips_timing(monkeypatch):
    """A ref-forced policy has nothing to tune — the sweep returns the
    incoming thresholds without running a single kernel."""
    monkeypatch.setenv("REPRO_LANE", LANE_REF)
    reg = MetricsRegistry()
    prof = autotune(ExecutionPolicy(registry=reg), trials=1)
    assert prof.hamming_block == DEFAULT_PROFILE.hamming_block
    assert prof.gram_block == DEFAULT_PROFILE.gram_block
    assert reg.counter("backend.autotune_candidates_timed").value == 0


def test_policy_profile_steers_plan_default(monkeypatch):
    """plan_for's hub_tile default resolves through the process policy's
    profile, not a frozen module constant."""
    from repro.backend.policy import set_default_policy
    from repro.core import similarity as sim_mod
    g = random_graph(200, 6.0, seed=5)
    try:
        set_default_policy(ExecutionPolicy(
            profile=AutotuneProfile(hub_tile=512)))
        assert sim_mod.plan_for(g).hub_tile == 512
    finally:
        set_default_policy(None)
    assert sim_mod.plan_for(g).hub_tile == 2048


def test_default_policy_singleton():
    pol = default_policy()
    assert default_policy() is pol
    assert pol.registry is not None
