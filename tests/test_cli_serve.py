"""``scan_serve serve``: exact and approximate-first traffic of each shape,
several indexes through one engine, and the telemetry flags."""
import json
import re

import pytest

from repro.core import (ApproxIndexBuilder, ApproxParams, build_index,
                        random_graph)
from repro.serve import IndexCatalog, LiveIndexService, index_fingerprint

TOY = ("--n", 256, "--avg-degree", 8, "--clients", 2, "--requests", 4)
# approximate-first cases: dense enough that 32-bit sketches replace σ on
# some edges, so the refine swap changes the served index
APPROX = ("--n", 256, "--avg-degree", 48, "--clients", 2, "--requests", 4,
          "--approx", "simhash:32")


def _fp(g, index):
    return index_fingerprint(index, g)[:12]


def _counts(line_prefix, out):
    line = next(ln for ln in out.splitlines() if ln.startswith(line_prefix))
    return {k: float(v) for k, v in re.findall(r"(\w+)=([\d.]+)", line)}


@pytest.mark.parametrize("traffic", ["global", "seed", "mixed"])
def test_serve_exact_traffic(scan_cli, traffic):
    out = scan_cli("serve", *TOY, "--traffic", traffic)
    g = random_graph(256, 8, seed=0)
    assert f"fingerprint={_fp(g, build_index(g, 'cosine'))})" in out
    assert f"8 {traffic} requests from 2 clients (1 indexes)" in out
    # the global path's device calls; seed requests have their own line
    assert (_counts("device calls=", out)["calls"] >= 1) == (
        traffic != "seed")
    if traffic == "global":
        assert "seed path:" not in out
    else:
        seed = _counts("seed path:", out)
        # one warm-up seed query precedes the timed traffic
        if traffic == "seed":
            assert seed["requests"] == 2 * 4 + 1
        else:
            assert 1 <= seed["requests"] <= 2 * 4 + 1
        assert seed["device_calls"] >= 1


@pytest.mark.parametrize("traffic", ["global", "seed", "mixed"])
def test_serve_approx_first_then_refined(scan_cli, traffic):
    """Traffic is served from the sketched index while ``refine`` builds
    the exact one; the swap lands before the run reports."""
    out = scan_cli("serve", *APPROX, "--traffic", traffic)
    g = random_graph(256, 48, seed=0)
    approx, prov = ApproxIndexBuilder(
        "cosine", ApproxParams.parse("simhash:32")).build(g)
    exact = _fp(g, build_index(g, "cosine"))
    assert exact != _fp(g, approx)
    assert "approx index 'idx0' built+serving in" in out
    assert f"fingerprint={_fp(g, approx)}, {prov.describe()})" in out
    assert re.search(rf"idx0: refined to exact in [\d.]+s → "
                     rf"fingerprint={exact} \(exact, seq=1\)", out)
    assert "approx_indexes_remaining=0" in out
    assert "live.refine: p50=" in out
    assert ("seed path:" in out) == (traffic != "global")


def test_serve_approx_save_restores_the_refined_index(scan_cli, tmp_path):
    d = tmp_path / "catalog"
    scan_cli("serve", *APPROX, "--save", d)
    g = random_graph(256, 48, seed=0)
    svc = LiveIndexService(str(d))
    assert svc.load("idx0") == index_fingerprint(build_index(g, "cosine"), g)
    assert svc.status("idx0")["provenance"] == "exact"


def test_serve_two_indexes_saved_to_a_catalog(scan_cli, tmp_path):
    d = tmp_path / "catalog"
    out = scan_cli("serve", *TOY, "--indexes", 2, "--save", d)
    printed = re.findall(r"built exact index .*fingerprint=([0-9a-f]{12})",
                         out)
    catalog = IndexCatalog(str(d))
    assert catalog.names() == ["idx0", "idx1"]
    for k, name in enumerate(catalog.names()):
        index, g, fp = catalog.store(name).load()
        assert g.m == random_graph(256, 8, seed=k).m
        assert fp[:12] == _fp(g, index) == printed[k]
    assert printed[0] != printed[1]
    assert len(catalog.load_all()) == 2
    assert "(2 indexes)" in out
    assert _counts("device calls=", out)["partitions"] == 2


def test_serve_metrics_json(scan_cli, tmp_path):
    path = tmp_path / "metrics.json"
    out = scan_cli("serve", *TOY, "--metrics-json", path)
    assert f"wrote metrics snapshot to {path}" in out
    snap = json.loads(path.read_text())
    # every client request plus the one warm-up query
    assert snap["counters"]["engine.requests"] == 2 * 4 + 1
    assert snap["histograms"]["engine.e2e"]["count"] >= 1


def test_serve_no_warm(scan_cli):
    out = scan_cli("serve", *TOY, "--no-warm")
    assert _counts("device calls=", out)["warmed"] == 0


def test_serve_stats_every_prints_periodic_lines(scan_cli):
    # a 10 ms flush window keeps traffic running across several 1 ms ticks
    out = scan_cli("serve", *TOY, "--stats-every", 0.001, "--flush-ms", 10,
                   "--no-warm")
    stats = [ln for ln in out.splitlines() if ln.startswith("stats: ")]
    assert stats
    assert all("engine.requests=" in ln for ln in stats)
