"""Sharded `query_batch` ≡ single-device `query_batch`, bit-exactly.

The sharded path (``repro.core.query_batch_sharded``) partitions the
half-edge and CO-slot arrays over a mesh ``data`` axis and finishes with
all-reduced label propagation; every merge is an associative min/max, so
results must equal the single-device path *exactly* — same labels, same
core mask, same cluster count, for every (μ, ε) including the extremes.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count=8`` (the parent process must keep
its real single-device view; see tests/test_distribution.py for the same
pattern).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    from repro.core import (build_index, from_edge_list, query_batch,
                            query_batch_sharded, query_mesh, random_graph)

    assert jax.device_count() == 8, jax.device_count()

    # μ sweeps past max_cdeg, ε hits both extremes (0 ⇒ every edge similar,
    # 1 ⇒ only σ=1 edges), plus interior settings.
    MUS  = np.asarray([2, 3, 4, 5, 2,   2,   10_000], np.int32)
    EPSS = np.asarray([0.0, 0.3, 0.5, 0.7, 1.0, 0.9, 0.5], np.float32)

    def check(g, mesh, tag):
        idx = build_index(g, "cosine")
        ref = query_batch(idx, g, MUS, EPSS)
        out = query_batch_sharded(idx, g, MUS, EPSS, mesh=mesh)
        for field in ("labels", "is_core", "n_clusters", "rounds"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out, field)),
                np.asarray(getattr(ref, field)),
                err_msg=f"{tag}:{field}")
        print("CASE_OK", tag, "n=", g.n, "m2=", g.m2,
              "ragged=", g.m2 % mesh.devices.size)

    mesh8 = query_mesh(8)

    # ragged edge count — padding to the axis size is exercised
    g = random_graph(97, 5.0, seed=3)
    assert g.m2 % 8 != 0, g.m2
    check(g, mesh8, "ragged-sparse")

    # weighted graph with planted structure
    g = random_graph(120, 8.0, seed=1, weighted=True, planted_clusters=4)
    check(g, mesh8, "weighted-planted")

    # isolated vertices + fewer edges than shards (every shard mostly pad)
    g = from_edge_list(10, [(0, 1), (1, 2), (7, 8)])
    assert g.m2 < 8, g.m2
    check(g, mesh8, "tiny-isolated")

    # a mesh that uses a strict subset of devices, with non-dividing size
    mesh3 = query_mesh(3)
    g = random_graph(64, 6.0, seed=9)
    check(g, mesh3, "three-way")

    print("ALL_OK")
""")


def _run_subprocess(prog: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/root")},
        cwd=_REPO, timeout=600)


@pytest.mark.slow
def test_sharded_query_batch_bit_exact_8way():
    """Acceptance criterion: the sharded query path matches the
    single-device path exactly on an 8-way forced host mesh, including
    ragged edge counts that need padding to the axis size."""
    r = _run_subprocess(_PROG)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ALL_OK" in r.stdout
    assert r.stdout.count("CASE_OK") == 4


def test_sharded_query_single_device_degenerate():
    """k=1 mesh in-process: the sharded code path (shard_map, collectives,
    padding) must already be exact with one shard — the cheap always-on
    guard; the 8-way proof lives in the slow lane. Also exercises
    ShardedQueryPlan reuse (pad once, query many — the engine's pattern)."""
    from repro.core import (ShardedQueryPlan, build_index, query_batch,
                            query_batch_sharded, query_mesh, random_graph)

    g = random_graph(60, 5.0, seed=7)
    idx = build_index(g, "cosine")
    mus = np.asarray([2, 3, 9999], np.int32)
    epss = np.asarray([0.0, 0.5, 1.0], np.float32)
    ref = query_batch(idx, g, mus, epss)
    out = query_batch_sharded(idx, g, mus, epss, mesh=query_mesh(1))
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.asarray(ref.labels))
    np.testing.assert_array_equal(np.asarray(out.is_core),
                                  np.asarray(ref.is_core))
    np.testing.assert_array_equal(np.asarray(out.n_clusters),
                                  np.asarray(ref.n_clusters))
    np.testing.assert_array_equal(np.asarray(out.rounds),
                                  np.asarray(ref.rounds))

    plan = ShardedQueryPlan(idx, g, query_mesh(1))
    for _ in range(2):                       # same plan, repeated calls
        out2 = plan(mus, epss)
        np.testing.assert_array_equal(np.asarray(out2.labels),
                                      np.asarray(ref.labels))


def test_query_mesh_rejects_oversubscription():
    from repro.core import query_mesh

    with pytest.raises(ValueError, match="devices"):
        query_mesh(4096)
