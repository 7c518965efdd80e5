"""The reader of ``similarity.queued_share.build``: nothing to read, or a
program without the ``similarity.chunks_queued`` counter, gives no
number; a traced rehearsal of the build cell gives a share in [0, 100]."""
import argparse

import pytest

from bench import harness, run

METRIC = "similarity.queued_share.build"


def _readings(counters):
    return run.Readings("cpu", {"counters": counters, "histograms": {}},
                        None, {"builds": 1})


@pytest.mark.parametrize("counters", [
    {},
    {"similarity.chunks": 0, "similarity.chunks_queued": 0},
    {"similarity.chunks": 12372},           # a program without the counter
])
def test_reader_gives_none_without_its_counters(counters):
    assert harness.load_module("layers", METRIC).read(
        _readings(counters)) is None


def test_reader_gives_the_queued_share_in_percent():
    got = harness.load_module("layers", METRIC).read(_readings(
        {"similarity.chunks": 200, "similarity.chunks_queued": 190}))
    assert got == pytest.approx(95.0)


def test_metric_is_in_the_spec_for_the_build_cell_only():
    spec = run.load_spec()
    assert METRIC in {m["name"] for m in run.per_layer_for(
        spec, "graph500-s18.build")}
    assert METRIC not in {m["name"] for m in run.per_layer_for(
        spec, "livejournal-s18.sweep")}


def test_traced_build_rehearsal_reports_the_share():
    args = argparse.Namespace(workload="graph500-s18.build", seed=2**33 + 11,
                              seconds=1.0, trace=1, rehearse=True)
    result, _ = run.execute(args)
    assert result["correct"]
    value = result["metrics"][run.REHEARSAL_PREFIX + METRIC]["value"]
    assert 0 <= value <= 100
