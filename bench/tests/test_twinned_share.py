"""The reader of ``similarity.twinned_share.build``: a program without the
``similarity.sigma_*`` counters (σ computed on every half-edge) gives no
number; a traced rehearsal of the build cell, where σ is computed once per
undirected edge, gives 50%."""
import argparse

import pytest

from bench import harness, run

METRIC = "similarity.twinned_share.build"


def _readings(counters):
    return run.Readings("cpu", {"counters": counters, "histograms": {}},
                        None, {"builds": 1})


@pytest.mark.parametrize("counters", [
    {},
    {"similarity.chunks": 12372, "similarity.chunks_queued": 12353},
    {"similarity.sigma_computed": 0, "similarity.sigma_twinned": 0},
])
def test_reader_gives_none_without_its_counters(counters):
    assert harness.load_module("layers", METRIC).read(
        _readings(counters)) is None


def test_reader_gives_the_twinned_share_in_percent():
    read = harness.load_module("layers", METRIC).read
    assert read(_readings({"similarity.sigma_computed": 300,
                           "similarity.sigma_twinned": 100})) == 25.0
    assert read(_readings({"similarity.sigma_computed": 300,
                           "similarity.sigma_twinned": 0})) == 0.0


def test_metric_is_in_the_spec_for_the_exact_build_cell():
    spec = run.load_spec()
    assert METRIC in {m["name"] for m in run.per_layer_for(
        spec, "graph500-s18.build")}
    assert METRIC not in {m["name"] for m in run.per_layer_for(
        spec, "livejournal-s18.sweep")}


def test_traced_build_rehearsal_reads_half_the_half_edges_twinned():
    args = argparse.Namespace(workload="graph500-s18.build", seed=2**33 + 17,
                              seconds=1.0, trace=1, rehearse=True)
    result, _ = run.execute(args)
    assert result["correct"]
    assert result["metrics"][run.REHEARSAL_PREFIX + METRIC]["value"] == 50.0
