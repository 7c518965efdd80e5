"""The approximate-first ingest cell: a sound rehearsal run is correct and
sketches edges, every check of ``bench/reference_lsh.py`` fires on its
fault, each control fails its check, and the ``hamming`` roofline share
counts the kernel's bytes by hand and stays at most 100%."""
import argparse
import dataclasses
import json
import re

import numpy as np
import pytest

from bench import control_lsh, harness, reference, reference_lsh, run, \
    trace_reduce

CELL = "graph500-s18-simhash256.approx-build"
CONFIG = "graph500-s18-simhash256"
READERS = ("lsh.sketch_device_s.approx", "hamming.device_s.approx",
           "hamming.hbm_share.approx", "lsh.host_s.approx")
# the exact build's metrics, read in this cell too
SHARED = ("similarity.device_s.build", "index.sort_device_s.build",
          "bucket_probe.device_s.build", "device.idle_share.build",
          "similarity.chunks.build", "store.save_s.build",
          "similarity.queued_share.build")


def _run(trace: int = 0) -> dict:
    args = argparse.Namespace(workload=CELL, seed=2**31 + 13, seconds=1.0,
                              trace=trace, rehearse=True)
    result, _ = run.execute(args, run.load_spec())
    return result


def test_sound_rehearsal_is_correct_and_sketches_edges(capsys):
    result = _run()
    assert result["correct"] is True
    assert set(result["checks"]) == {
        "graph_bad", "exact_sigma_bad", "sketch_h_off", "sketch_h_far",
        "sketch_sigma_ulps", "theta_tail_share", "order_bad", "unanswered"}
    # a largest value over builds, not a sum: a CPU's cos is 1 ulp off at most
    assert result["checks"]["sketch_sigma_ulps"]["value"] <= 1
    err = capsys.readouterr().err
    counters = json.loads(re.search(r"counters over the window: (\{.*\})",
                                    err).group(1))
    assert counters["lsh.sketched_edges"] > 0
    assert counters["lsh.exact_edges"] > 0


def flipped_sketch_bit(monkeypatch):
    import jax.numpy as jnp
    import repro.core.lsh as lsh
    orig = lsh.simhash_sketches

    def broken(g, *a, **k):
        sk = orig(g, *a, **k)
        hub = int(np.argmax(np.diff(np.asarray(g.offsets))))
        return sk.at[hub, 0].set(sk[hub, 0] ^ jnp.uint32(1))
    monkeypatch.setattr(lsh, "simhash_sketches", broken)


def exact_sigma_one_ulp(monkeypatch):
    import jax.numpy as jnp
    import repro.core.similarity as sim
    orig = sim.SimilarityPlan.edge_sims

    def broken(self, *a, **k):
        s = orig(self, *a, **k)
        return s.at[0].set(jnp.nextafter(s[0], jnp.float32(0)))
    monkeypatch.setattr(sim.SimilarityPlan, "edge_sims", broken)


def sigma_hat_from_64_samples(monkeypatch):
    from repro.kernels import ops
    orig = ops.simhash_edge_similarity_kernel

    def broken(sketches, eu, ev, samples, *a, **k):
        return orig(sketches[:, :2], eu, ev, 64, *a, **k)
    monkeypatch.setattr(ops, "simhash_edge_similarity_kernel", broken)


def sigma_hat_in_bfloat16(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    orig = ops.simhash_edge_similarity_kernel

    def broken(*a, **k):
        return orig(*a, **k).astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(ops, "simhash_edge_similarity_kernel", broken)


def no_slot_swapped(monkeypatch):
    import jax.numpy as jnp
    import repro.core.approx as approx
    orig = approx.build_index

    def broken(g, *a, **k):
        idx = orig(g, *a, **k)
        v = int(np.flatnonzero(np.asarray(idx.cdeg) >= 2)[0])
        at = int(np.asarray(idx.offsets_c)[v])
        nb = np.asarray(idx.no_nbrs).copy()
        nb[[at, at + 1]] = nb[[at + 1, at]]
        return dataclasses.replace(idx, no_nbrs=jnp.asarray(nb))
    monkeypatch.setattr(approx, "build_index", broken)


FAULTS = [
    (flipped_sketch_bit, "sketch_h_far"),
    (exact_sigma_one_ulp, "exact_sigma_bad"),
    (sigma_hat_from_64_samples, "theta_tail_share"),
    (sigma_hat_in_bfloat16, "sketch_sigma_ulps"),
    (no_slot_swapped, "order_bad"),
]


@pytest.mark.parametrize("fault,check", FAULTS,
                         ids=[f.__name__ for f, _ in FAULTS])
def test_each_check_fires_on_its_fault(monkeypatch, fault, check):
    fault(monkeypatch)
    result = _run()
    assert result["correct"] is False
    c = result["checks"][check]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("control", sorted(control_lsh.CONTROLS))
def test_control_fails_its_check(control):
    got = control_lsh.readings(CELL, seed=2**31 + 3, rehearse=True,
                               controls=(control,))[control]
    assert got["graph_bad"]["value"] == 0
    assert got["exact_sigma_bad"]["value"] == 0
    must = got[control_lsh.CONTROLS[control][1]]
    assert must["value"] > must["limit"]


def test_reference_h_is_popcount_of_float64_sketches():
    cfg = harness.load_json("configs", CONFIG)
    rg = harness.make_graph(cfg, seed=9, rehearse=True)
    ref = reference_lsh.RefApprox.build(rg.reference(), cfg["sketch"])
    assert 0 < ref.sketched.sum() < len(ref.nbrs)
    k = cfg["sketch"]["samples"]
    # σ̂ of the reference's own h reads back to that h (clipped at k/2)
    np.testing.assert_array_equal(
        reference_lsh.h_of(ref.served_sims()[ref.sketched], k),
        np.minimum(ref.h, k // 2))
    # every sketched half-edge and its twin share one h
    key = dict(zip(zip(ref.edge_u[ref.sketched].tolist(),
                       ref.nbrs[ref.sketched].tolist()), ref.h.tolist()))
    assert all(key[(v, u)] == h for (u, v), h in key.items())
    # the float64 h lies inside what float32 rounding allows
    assert np.all((ref.h_lo <= ref.h) & (ref.h <= ref.h_hi))


def test_float32_sums_stay_inside_the_rounding_interval():
    """h from float32 sums, added in an order of their own, never leaves
    [h_lo, h_hi]; and the interval is exact (width 0) on most edges."""
    cfg = harness.load_json("configs", CONFIG)
    rg = harness.make_graph(cfg, seed=9, rehearse=True)
    graph = rg.reference()
    ref = reference_lsh.RefApprox.build(graph, cfg["sketch"])
    offsets, eu, nb, _ = graph.csr()
    k = cfg["sketch"]["samples"]
    hubs = np.flatnonzero(np.diff(offsets) + 1 > k)
    r = reference_lsh.projections(len(offsets) - 1, k,
                                  cfg["sketch"]["seed"])
    rng = np.random.default_rng(0)
    s = np.zeros((len(hubs), k), np.float32)
    for i, v in enumerate(hubs):
        terms = np.concatenate([r[[v]], r[nb[offsets[v]:offsets[v + 1]]]])
        for t in terms[rng.permutation(len(terms))]:
            s[i] += t
    bits = np.packbits(s >= 0, axis=1)
    row = np.full(len(offsets) - 1, -1)
    row[hubs] = np.arange(len(hubs))
    h = reference_lsh.popcount(bits[row[eu[ref.sketched]]]
                               ^ bits[row[nb[ref.sketched]]])
    assert np.all((ref.h_lo <= h) & (h <= ref.h_hi))
    assert np.mean(ref.h_lo == ref.h_hi) > 0.5


def test_ulps_counts_float32_steps():
    one = np.float32(1.0)
    assert reference_lsh.ulps(one, np.nextafter(one, np.float32(2))) == 1
    assert reference_lsh.ulps(np.float32(0.0), np.float32(-0.0)) == 0
    tiny = np.nextafter(np.float32(0), np.float32(1))
    assert reference_lsh.ulps(-tiny, tiny) == 2


def test_configuration_counts_at_cell_size():
    cfg = harness.load_json("configs", CONFIG)
    rg = harness.make_graph(cfg, seed=0)
    g = reference.Graph.from_pairs(rg.n, rg.base)
    cdeg = g.degrees() + 1
    high = cdeg > cfg["sketch"]["samples"]
    counts = cfg["counts"]
    assert g.m == counts["m"] and int(high.sum()) == counts["hubs_over_k"]
    assert 2 * int((high[g.lo] & high[g.hi]).sum()) == counts["sketched_m2"]


def _readings(programs, kind="TPU v5 lite", extra=None):
    trace = trace_reduce.Reduced(window_s=10.0, busy_s=6.0, programs={},
                                 kernels={}, top_programs=programs, gaps=[])
    if extra is None:
        extra = {"builds": 2, "m2": 1000, "sketch_words": 8}
    return run.Readings(kind, {"counters": {}, "histograms": {}}, trace,
                        extra)


def test_hamming_share_counts_bytes_by_hand():
    share = harness.load_module("layers", "hamming.hbm_share.approx")
    # per build: two 1000 × 8 uint32 operands read, 1000 int32 written
    assert share.hamming_bytes(1000, 8) == 2 * 1000 * 8 * 4 + 1000 * 4
    r = _readings([("jit_hamming_distance", 2e-4),
                   ("jit__simhash_chunk", 5.0)])
    want = 100.0 * 2 * 68000 / (2e-4 * 819e9)
    assert share.read(r) == pytest.approx(want)
    assert 0 < share.read(r) <= 100.0
    dev = harness.load_module("layers", "hamming.device_s.approx")
    assert dev.read(r) == pytest.approx(1e-4)
    with pytest.raises(KeyError):
        share.read(_readings([("jit_hamming_distance", 2e-4)], kind="cpu"))


@pytest.mark.parametrize("metric", READERS)
def test_reader_without_readings_gives_none(metric):
    empty = run.Readings("cpu", {"counters": {}, "histograms": {}}, None,
                         {"builds": 2})
    assert harness.load_module("layers", metric).read(empty) is None
    assert metric in {m["name"] for m in run.per_layer_for(run.load_spec(),
                                                           CELL)}


def test_cell_reports_the_exact_builds_metrics():
    names = {m["name"] for m in run.per_layer_for(run.load_spec(), CELL)}
    assert names == set(READERS) | set(SHARED)


def test_traced_rehearsal_reports_the_span_reader():
    result = _run(trace=1)
    assert result["correct"]
    # on a CPU no device plane is found: the trace readers give nothing
    for metric in ("lsh.host_s.approx", "similarity.chunks.build",
                   "store.save_s.build", "similarity.queued_share.build"):
        assert result["metrics"][run.REHEARSAL_PREFIX + metric]["value"] > 0
