"""``scan_serve sweep``: the printed (μ, ε) table against
``repro.serve.sweep_stats`` on the same graph and index, the
``--save``/``--load`` round trip, and the graph flags."""
import re

import pytest

from repro.core import (ApproxIndexBuilder, ApproxParams, build_index,
                        random_graph)
from repro.serve import IndexStore, index_fingerprint, sweep_stats

MUS, EPSS = "2,4", "0.3:0.7:3"
GRID = ("--mus", MUS, "--epss", EPSS)


def _fingerprints(out):
    return re.findall(r"fingerprint=([0-9a-f]{12})", out)


def _table(out):
    """The printed table: header, one row per setting, best setting."""
    return out[out.index("  mu "):]


def _expected_table(index, g):
    """The table ``cmd_sweep`` prints, from ``sweep_stats`` directly."""
    rows = sweep_stats(index, g, [2, 4], [0.3, 0.5, 0.7])
    lines = [f"{r['mu']:>4} {r['eps']:>6.2f} {r['n_clusters']:>9} "
             f"{r['n_cores']:>7} {r['coverage']:>9.3f} "
             f"{r['modularity']:>11.4f}" for r in rows]
    best = max(rows, key=lambda r: r["modularity"])
    lines.append(f"best modularity: mu={best['mu']} eps={best['eps']:.2f} "
                 f"Q={best['modularity']:.4f}")
    return "\n".join(lines)


def _index(g, measure, approx):
    if approx is None:
        return build_index(g, measure)
    index, _ = ApproxIndexBuilder(measure, ApproxParams.parse(approx)).build(g)
    return index


# approximate cases run at degree 48 with 32 samples, so the §6.3 rule
# (both closed degrees above k, or 3k/2 for jaccard) sketches real edges
@pytest.mark.parametrize("measure,weighted,approx,deg", [
    ("cosine", False, None, 8),
    ("jaccard", False, None, 8),
    ("cosine", True, None, 8),
    ("cosine", False, "simhash:32", 48),
    ("jaccard", False, "minhash:32", 48),
], ids=["cosine", "jaccard", "weighted", "simhash", "minhash"])
def test_sweep_table_matches_sweep_stats(scan_cli, measure, weighted, approx,
                                         deg):
    argv = ["sweep", "--n", 256, "--avg-degree", deg, "--measure", measure,
            *GRID]
    if weighted:
        argv.append("--weighted")
    if approx:
        argv += ["--approx", approx]
    out = scan_cli(*argv)

    g = random_graph(256, deg, seed=0, weighted=weighted)
    index = _index(g, measure, approx)
    assert _fingerprints(out) == [index_fingerprint(index, g)[:12]]
    assert _expected_table(index, g) in out
    assert "6 (μ, ε) settings in one vmapped call" in out
    if approx:
        # the sketches changed σ on some edge, so this is not the exact index
        exact = build_index(g, measure)
        assert index_fingerprint(exact, g) != index_fingerprint(index, g)
        assert "built approx(" in out


@pytest.mark.parametrize("approx", [None, "simhash:32"],
                         ids=["exact", "approx"])
def test_sweep_save_then_load(scan_cli, tmp_path, approx):
    d = tmp_path / "idx"
    argv = ["sweep", "--n", 256, "--avg-degree", 48, *GRID]
    if approx:
        argv += ["--approx", approx]
    built = scan_cli(*argv, "--save", d)
    assert f"persisted to {d}" in built
    loaded = scan_cli("sweep", "--load", d, *GRID)

    store = IndexStore(str(d))
    provenance = store.provenance()
    assert provenance.is_approx == (approx is not None)
    assert f"loaded index v{store.latest_version()} from {d}" in loaded
    assert provenance.describe() in loaded
    assert _fingerprints(built) == _fingerprints(loaded)
    assert _table(built) == _table(loaded)


def test_update_load_refuses_a_store_of_another_measure(scan_cli, tmp_path):
    d = tmp_path / "idx"
    g = random_graph(64, 6.0, seed=0)
    IndexStore(str(d)).save(build_index(g, "jaccard"), g, measure="jaccard")
    with pytest.raises(SystemExit, match="built with --measure jaccard"):
        scan_cli("update", "--load", d, "--measure", "cosine",
                 "--root", tmp_path / "live")


def test_clusters_flag_builds_the_planted_graph(scan_cli):
    out = scan_cli("sweep", "--n", 256, "--avg-degree", 8, "--clusters", 4,
                   *GRID)
    g = random_graph(256, 8, seed=0, planted_clusters=4)
    flat = random_graph(256, 8, seed=0)
    assert f"(n=256, m={g.m}, seed=0," in out
    fp = index_fingerprint(build_index(g, "cosine"), g)[:12]
    assert _fingerprints(out) == [fp]
    assert fp != index_fingerprint(build_index(flat, "cosine"), flat)[:12]
