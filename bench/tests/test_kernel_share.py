"""The reader of ``similarity.kernel_share.build``: a program without the
``similarity.sigma_kernel`` counter gives no number; with it, the share of
computed half-edges a Pallas lane computed, in percent. A traced rehearsal
on the CPU, whose σ groups run the ref lane, reads 0."""
import argparse

import pytest

from bench import harness, run

METRIC = "similarity.kernel_share.build"


def _readings(counters):
    return run.Readings("cpu", {"counters": counters, "histograms": {}},
                        None, {"builds": 1})


@pytest.mark.parametrize("counters", [
    {},
    {"similarity.sigma_computed": 3806359,
     "similarity.sigma_twinned": 3806359},
    {"similarity.sigma_computed": 0, "similarity.sigma_kernel": 0},
])
def test_reader_gives_none_without_its_counter(counters):
    assert harness.load_module("layers", METRIC).read(
        _readings(counters)) is None


def test_reader_gives_the_kernel_share_in_percent():
    read = harness.load_module("layers", METRIC).read
    assert read(_readings({"similarity.sigma_computed": 400,
                           "similarity.sigma_kernel": 100})) == 25.0
    assert read(_readings({"similarity.sigma_computed": 400,
                           "similarity.sigma_kernel": 400})) == 100.0
    assert read(_readings({"similarity.sigma_computed": 400,
                           "similarity.sigma_kernel": 0})) == 0.0


def test_metric_is_in_the_spec_for_the_exact_build_cell():
    spec = run.load_spec()
    assert METRIC in {m["name"] for m in run.per_layer_for(
        spec, "graph500-s18.build")}
    for cell in ("graph500-s18-simhash256.approx-build",
                 "livejournal-s18.sweep"):
        assert METRIC not in {m["name"] for m in run.per_layer_for(
            spec, cell)}


def test_traced_build_rehearsal_on_the_cpu_reads_no_kernel_share(
        monkeypatch):
    monkeypatch.delenv("REPRO_LANE", raising=False)
    args = argparse.Namespace(workload="graph500-s18.build", seed=2**33 + 19,
                              seconds=1.0, trace=1, rehearse=True)
    result, _ = run.execute(args)
    assert result["correct"]
    assert result["metrics"][run.REHEARSAL_PREFIX + METRIC]["value"] == 0.0
