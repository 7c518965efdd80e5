"""The readers of the program's spans and counters, and the split of a
window's idle device time by span: nothing to read gives no number, a
traced rehearsal run gives one for every reader of its cell."""
import argparse

import pytest

from bench import harness, idle_split, run

READERS = {
    "graph500-s18.build": ("similarity.host_s.build",
                           "similarity.chunks.build", "store.save_s.build"),
    "livejournal-s18.sweep": ("query.rounds_per_call.sweep",
                              "query.lane_round_use.sweep",
                              "engine.host_ms.sweep"),
}
ALL = [m for names in READERS.values() for m in names]


@pytest.mark.parametrize("metric", ALL)
def test_reader_without_readings_gives_none(metric):
    empty = run.Readings("cpu", {"counters": {}, "histograms": {}}, None,
                         {"builds": 2})
    assert harness.load_module("layers", metric).read(empty) is None


def test_every_reader_is_in_the_spec():
    spec = run.load_spec()
    for cell, names in READERS.items():
        assert set(names) <= {m["name"] for m in run.per_layer_for(spec,
                                                                   cell)}


def test_split_idle_gives_each_instant_to_the_innermost_span():
    spans = [("live.create", 0, 100), ("similarity.pass", 10, 60),
             ("similarity.group", 20, 30)]
    gaps = [(5, 15), (25, 40), (90, 120)]
    got = idle_split.split_idle(gaps, spans, 0, 110)
    assert got == {"live.create": 5 + 10, "similarity.pass": 5 + 10,
                   "similarity.group": 5, idle_split.OUTSIDE: 10}


@pytest.mark.parametrize("cell", sorted(READERS))
def test_traced_rehearsal_reports_every_reader(cell):
    args = argparse.Namespace(workload=cell, seed=2**33 + 7, seconds=1.0,
                              trace=1, rehearse=True)
    result, _ = run.execute(args)
    assert result["correct"]
    for name in READERS[cell]:
        value = result["metrics"][run.REHEARSAL_PREFIX + name]["value"]
        assert value >= 0
    split = idle_split.split_file(
        sorted(harness.TRACES.glob("**/*.xplane.pb"))[-1],
        idle_split.trace_reduce.load_patterns())
    # the program's spans reach the profiler's host plane (on a CPU no
    # device plane is found, so there is no idle time to split)
    assert split["spans"] > 0
    assert sum(split["idle_by_span"].values()) == pytest.approx(
        split["idle_s"])
