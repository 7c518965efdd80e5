"""A configuration, a traffic mix and a per-layer metric added as files
are found by the names in the benchmark's spec, with no edit to any file
that is already there."""
import argparse
import json

from bench import harness, run


def test_new_files_are_found_by_name(tmp_path):
    cfg = harness.load_json("configs", "livejournal-s18")
    cfg["rehearsal"]["n"] = 1536
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tiny-graph.json").write_text(json.dumps(cfg))
    walk = harness.load_json("traffic", "sweep")
    walk.update(clients=4, sample_keys=2)
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "walk.json").write_text(json.dumps(walk))
    (tmp_path / "layers").mkdir()
    (tmp_path / "layers" / "engine.requests.walk.py").write_text(
        "def read(r):\n    return r.counter('engine.requests')\n")
    spec = {
        "workloads": [{"name": "tiny-graph.walk", "config": "tiny-graph",
                       "traffic": "walk", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "sweep_p95_s", "unit": "s", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-graph.walk"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "engine.requests.walk", "unit": "requests",
             "better": "higher", "source": "program_counter",
             "layer": "engine", "moves": "sweep_p95_s",
             "workloads": ["tiny-graph.walk"]}],
    }
    args = argparse.Namespace(workload="tiny-graph.walk", seed=2**31 + 5,
                              seconds=1.0, trace=0, rehearse=True)
    result, _ = run.execute(args, spec, base=tmp_path)
    assert result["correct"]
    assert set(result["metrics"]) == {"rehearsal.sweep_p95_s",
                                      "rehearsal.setup_s"}
    args.trace = 1
    result, _ = run.execute(args, spec, base=tmp_path)
    assert result["correct"]
    assert result["metrics"]["rehearsal.engine.requests.walk"]["value"] > 0
    assert list(result)[-1] == "checks"
