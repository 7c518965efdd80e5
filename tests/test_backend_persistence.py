"""Autotune-profile persistence: the ``backend_profile`` manifest leaf.

Three guarantees: a profile round-trips through ``IndexStore`` save/load;
checkpoints written *before* the leaf existed restore to the untuned
default — bit-for-bit today's constants, so old indexes behave exactly as
they always did; and a snapshot saved under a different profile than the
serving policy's surfaces as ``profile_mismatch`` in
``LiveIndexService.status()`` instead of silently retuning.
"""
import asyncio

import pytest

from repro.backend.policy import ExecutionPolicy
from repro.core import EdgeDelta
from repro.backend.profile import DEFAULT_PROFILE, AutotuneProfile
from repro.core import build_index, random_graph
from repro.serve import EngineConfig, IndexStore, LiveIndexService
from repro.serve import store as store_mod


def _graph(n=60, deg=6.0, seed=1):
    return random_graph(n, deg, seed=seed)


def test_profile_roundtrips_through_store(tmp_path):
    g = _graph()
    index = build_index(g, "cosine")
    tuned = AutotuneProfile(platform="cpu", gram_block=64, probe_be=128,
                            hamming_block=512)
    store = IndexStore(str(tmp_path))
    store.save(index, g, profile=tuned)
    assert store.profile() == tuned
    # a later version may carry different thresholds; each reads back its own
    store.save(index, g, profile=DEFAULT_PROFILE)
    assert store.profile() == DEFAULT_PROFILE
    assert store.profile(version=0) == tuned


def test_save_without_profile_persists_default(tmp_path):
    g = _graph(seed=2)
    index = build_index(g, "cosine")
    store = IndexStore(str(tmp_path))
    store.save(index, g)
    assert store.profile() == DEFAULT_PROFILE


def test_old_checkpoint_without_leaf_defaults(tmp_path, monkeypatch):
    """A checkpoint written before the leaf existed (simulated by dropping
    it from the tree) restores to the untuned default — the exact
    constants the engine ran with before autotune existed."""
    real_to_tree = store_mod._to_tree

    def legacy_to_tree(*args, **kw):
        tree = real_to_tree(*args, **kw)
        tree.pop("backend_profile")
        return tree

    monkeypatch.setattr(store_mod, "_to_tree", legacy_to_tree)
    g = _graph(seed=3)
    index = build_index(g, "cosine")
    store = IndexStore(str(tmp_path))
    store.save(index, g, profile=AutotuneProfile(gram_block=64))
    monkeypatch.undo()
    prof = store.profile()
    assert prof == DEFAULT_PROFILE
    assert prof.to_json() == DEFAULT_PROFILE.to_json()   # bit-for-bit
    # and the index itself still loads
    index2, g2, _ = store.load()
    assert index2.n == index.n


def test_stored_profile_with_a_retired_key_still_loads(tmp_path,
                                                      monkeypatch):
    """A snapshot saved while the profile still had ``probe_min_width``
    (the retired width cutoff of the σ pass's lane choice) loads: the
    retired key is dropped, every other stored value is kept, and an
    untuned stored profile equals today's default."""
    import json

    import numpy as np

    def legacy(profile):
        data = json.loads(profile.to_json())
        data["probe_min_width"] = 256
        return np.frombuffer(json.dumps(data, sort_keys=True).encode(),
                             np.uint8)

    real_to_tree = store_mod._to_tree

    def legacy_to_tree(*args):
        tree = real_to_tree(*args)
        tree["backend_profile"] = legacy(args[-1])
        return tree

    monkeypatch.setattr(store_mod, "_to_tree", legacy_to_tree)
    g = _graph(seed=7)
    index = build_index(g, "cosine")
    store = IndexStore(str(tmp_path))
    tuned = AutotuneProfile(platform="tpu", probe_be=128)
    store.save(index, g, profile=tuned)
    store.save(index, g, profile=DEFAULT_PROFILE)
    monkeypatch.undo()
    assert b"probe_min_width" in bytes(legacy(DEFAULT_PROFILE))
    assert store.profile(version=0) == tuned
    assert store.profile() == DEFAULT_PROFILE
    index2, _, _ = store.load()
    assert index2.n == index.n


def test_profile_mismatch_surfaces_in_status(tmp_path):
    """Restore under a policy tuned differently than the snapshot: the
    service flags the mismatch in status(), keeps serving on the policy's
    thresholds, and the next compaction (which re-persists under the
    serving profile) clears it."""
    g = _graph(seed=4)
    cfg = EngineConfig(max_batch=8, flush_ms=5.0)

    saved_profile = AutotuneProfile(platform="cpu", hamming_block=512)
    svc1 = LiveIndexService(
        str(tmp_path), config=cfg,
        policy=ExecutionPolicy(profile=saved_profile))
    svc1.create("web", g)
    assert svc1.status("web")["backend"]["profile_mismatch"] is False

    serving_profile = AutotuneProfile(platform="cpu", hamming_block=1024,
                                      gram_block=64)
    svc2 = LiveIndexService(
        str(tmp_path), config=cfg,
        policy=ExecutionPolicy(profile=serving_profile))
    svc2.load("web")
    backend = svc2.status("web")["backend"]
    assert backend["profile_mismatch"] is True
    assert backend["stored_profile"]["hamming_block"] == 512
    # serving continues on the policy's thresholds, not the stored ones
    assert backend["profile"]["hamming_block"] == 1024

    async def main():
        async with svc2:
            res = await svc2.query("web", 2, 0.5)
            assert res.n_clusters >= 0
            # advance past snapshot v0 (versions are monotone), then
            # compact: the fresh snapshot carries the serving profile
            await svc2.apply("web", EdgeDelta.make(
                inserts=[(0, 30)], weights=[0.9]))
            svc2.compact("web")
            assert svc2.status("web")["backend"]["profile_mismatch"] is False

    asyncio.run(main())
    # and a fresh restore now agrees with the serving policy
    svc3 = LiveIndexService(
        str(tmp_path), config=cfg,
        policy=ExecutionPolicy(profile=serving_profile))
    svc3.load("web")
    assert svc3.status("web")["backend"]["profile_mismatch"] is False


def test_status_backend_block_shape(tmp_path, monkeypatch):
    # the env var beats EngineConfig(lane=...) by design; clear it so the
    # config-lane assertion below sees the config, not the CI matrix lane
    monkeypatch.delenv("REPRO_LANE", raising=False)
    svc = LiveIndexService(str(tmp_path),
                           config=EngineConfig(max_batch=8, flush_ms=5.0))
    svc.create("web", _graph(seed=5))
    backend = svc.status("web")["backend"]
    assert set(backend) >= {"platform", "forced_lane", "lanes", "profile",
                            "profile_mismatch"}
    assert "bucket_probe" in backend["lanes"]
    # engine config lane flows into the policy the block describes
    svc2 = LiveIndexService(
        str(tmp_path) + "_b",
        config=EngineConfig(max_batch=8, flush_ms=5.0, lane="ref"))
    svc2.create("web", _graph(seed=6))
    assert svc2.status("web")["backend"]["forced_lane"] == "ref"


def test_engine_lane_counters_in_registry(tmp_path):
    """backend.lane.* counters land in the engine's own registry — one
    scrape covers engine.* and backend.* alike."""
    svc = LiveIndexService(str(tmp_path),
                           config=EngineConfig(max_batch=8, flush_ms=5.0))
    svc.create("web", _graph(seed=7))

    async def main():
        async with svc:
            await svc.query("web", 2, 0.5)

    asyncio.run(main())
    counters = svc.engine.registry.snapshot()["counters"]
    assert counters.get("backend.lane.query.ref", 0) >= 1
