"""``scan_serve update`` and ``scan_serve fleet``: edit streams under
traffic, whose catalog a fresh process restores to the fingerprint the run
printed, and the replicated fleet's bit-identity check with and without
chaos."""
import json
import re

import pytest

from repro.core import build_index, random_graph
from repro.serve import DeltaLog, IndexStore, LiveIndexService

TOY = ("--n", 256, "--avg-degree", 8, "--clients", 2, "--requests", 4)


def _final(out):
    """(seq, snapshot_seq, fingerprint) from the ``updates:`` line."""
    m = re.search(r"final seq=(\d+) snapshot_seq=(\d+) "
                  r"fingerprint=([0-9a-f]{12})", out)
    return int(m[1]), int(m[2]), m[3]


def _restored(root):
    svc = LiveIndexService(str(root))
    return svc.load("live"), svc.status("live")


@pytest.mark.parametrize("traffic", ["global", "seed", "mixed"])
def test_update_traffic_replays_to_the_printed_fingerprint(scan_cli, tmp_path,
                                                           traffic):
    root = tmp_path / "live"
    out = scan_cli("update", *TOY, "--traffic", traffic, "--updates", 3,
                   "--update-batch", 4, "--root", root)
    assert "8 queries under 3 live update batches (size 4)" in out
    seq, snapshot_seq, fp = _final(out)
    assert (seq, snapshot_seq) == (3, 0)
    restored, status = _restored(root)
    assert restored.startswith(fp)
    assert status["seq"] == 3
    assert ("seed cache vs deltas: migrated=" in out) == (traffic != "global")
    assert "apply pipeline: apply_delta p50=" in out


def test_update_compacts_every_k_deltas(scan_cli, tmp_path):
    root = tmp_path / "live"
    out = scan_cli("update", *TOY, "--updates", 3, "--update-batch", 4,
                   "--compact-every", 2, "--root", root)
    seq, snapshot_seq, fp = _final(out)
    assert (seq, snapshot_seq) == (3, 2)
    store = IndexStore(str(root / "live"))
    assert store.latest_version() == 2
    # the chain prefix the snapshot covers is pruned; delta 3 replays
    assert DeltaLog(store.directory).sequences() == [3]
    restored, status = _restored(root)
    assert restored.startswith(fp)
    assert status["snapshot_seq"] == 2


def test_update_load_adopts_a_saved_index(scan_cli, tmp_path):
    d = tmp_path / "idx"
    g = random_graph(200, 6.0, seed=4)
    IndexStore(str(d)).save(build_index(g, "cosine"), g, measure="cosine")
    _, _, saved_fp = IndexStore(str(d)).load()
    out = scan_cli("update", "--load", d, "--clients", 2, "--requests", 4,
                   "--updates", 2, "--update-batch", 4,
                   "--root", tmp_path / "live")
    assert (f"live index adopted from {d} in" in out
            and f"(n=200, m={g.m}, fingerprint={saved_fp[:12]})" in out)
    assert _final(out)[0] == 2


def test_update_metrics_json_holds_the_apply_pipeline(scan_cli, tmp_path):
    path = tmp_path / "metrics.json"
    out = scan_cli("update", *TOY, "--updates", 2, "--update-batch", 4,
                   "--root", tmp_path / "live", "--metrics-json", path)
    assert f"wrote metrics snapshot to {path}" in out
    hist = json.loads(path.read_text())["histograms"]
    assert hist["live.apply"]["count"] == 2
    assert hist["live.apply_delta"]["count"] == 2


@pytest.mark.parametrize("chaos", [None, "crash:0.02,stall:0.05,corrupt:0.1"],
                         ids=["clean", "chaos"])
def test_fleet_check_passes(scan_cli, tmp_path, chaos):
    argv = ["fleet", *TOY, "--replicas", 2, "--updates", 3,
            "--update-batch", 4, "--traffic", "mixed",
            "--root", tmp_path / "fleet"]
    if chaos:
        argv += ["--chaos", chaos, "--chaos-seed", 7]
    out = scan_cli(*argv)
    assert ("armed chaos(seed=7, " in out) == (chaos is not None)
    assert "divergent=0 unshed_timeouts=0" in out
    assert out.rstrip().endswith(
        "fleet check passed: every answer carried the writer's exact bits "
        "for its sequence number")
    if chaos is None:
        assert "injected_corruptions=0 crashes=0 stalls=0" in out
        assert "fleet converged; writer at seq 3" in out
        assert len(re.findall(
            r"replica-\d: healthy=True crashed=False seq=3/3", out)) == 2


def test_fleet_metrics_json_merges_the_router(scan_cli, tmp_path):
    path = tmp_path / "fleet.json"
    out = scan_cli("fleet", *TOY, "--replicas", 2, "--updates", 2,
                   "--update-batch", 4, "--root", tmp_path / "fleet",
                   "--metrics-json", path)
    assert f"wrote merged fleet metrics snapshot to {path}" in out
    counters = json.loads(path.read_text())["counters"]
    # every client request plus the one warm-up query, all routed
    assert counters["fleet.requests"] >= 2 * 4 + 1
    ok = int(re.search(r"answers: ok=(\d+)", out)[1])
    assert ok == 2 * 4
