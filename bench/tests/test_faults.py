"""Runs of each cell, at the rehearsal size and without the look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for every fault the cell can have. (One chip: there is no exchange
between chips to leave out.)"""
import argparse
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run


@pytest.fixture
def base(tmp_path):
    """The benchmark's files, but with the sweep checking every (μ, ε)
    it answered: at the rehearsal size that costs little, and a fault
    that spares some answers is then caught on every run, not on most."""
    sweep = harness.load_json("traffic", "sweep")
    sweep["sample_keys"] = 10 ** 6
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "sweep.json").write_text(json.dumps(sweep))
    return tmp_path


def _run(workload: str, base=harness.BENCH) -> dict:
    seconds = 1.0 if workload.endswith("build") else 2.0
    args = argparse.Namespace(workload=workload, seed=2**31 + 11,
                              seconds=seconds, trace=0, rehearse=True)
    result, _ = run.execute(args, run.load_spec(), base=base)
    return result


def sigma_altered(monkeypatch):
    import repro.core.similarity as sim
    orig = sim.compute_similarities

    def broken(g, *a, **k):
        return orig(g, *a, **k).at[0].add(jnp.float32(1e-3))
    monkeypatch.setattr(sim, "compute_similarities", broken)


def answer_altered(monkeypatch):
    import repro.serve.engine as engine
    orig = engine.query_batch

    def broken(index, g, mus, epss):
        res = orig(index, g, mus, epss)
        labels = np.asarray(res.labels).copy()
        labels[:, 0] = np.where(labels[:, 0] == 1, 2, 1)
        return type(res)(labels=labels, is_core=res.is_core,
                         n_clusters=res.n_clusters)
    monkeypatch.setattr(engine, "query_batch", broken)


def half_batch(monkeypatch):
    import repro.serve.engine as engine
    orig = engine.query_batch

    def broken(index, g, mus, epss):
        half = len(mus) // 2
        mus, epss = np.asarray(mus).copy(), np.asarray(epss).copy()
        mus[half:], epss[half:] = mus[0], epss[0]
        return orig(index, g, mus, epss)
    monkeypatch.setattr(engine, "query_batch", broken)


def state_unchanged(monkeypatch):
    import repro.serve.live as live
    orig = live.build_index
    first = []

    def broken(g, *a, **k):
        if not first:
            first.append(orig(g, *a, **k))
        return first[0]
    monkeypatch.setattr(live, "build_index", broken)


CASES = [
    ("livejournal-s18.sweep", sigma_altered),
    ("livejournal-s18.sweep", answer_altered),
    ("livejournal-s18.sweep", half_batch),
    ("graph500-s18.build", sigma_altered),
    ("graph500-s18.build", state_unchanged),
]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__}" for w, f in CASES])
def test_fault_is_not_correct(monkeypatch, base, workload, fault):
    fault(monkeypatch)
    result = _run(workload, base)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("workload", sorted({w for w, _ in CASES}))
def test_sound_run_is_correct(base, workload):
    result = _run(workload, base)
    assert result["correct"] is True
