"""Crash-recovery coverage for the durable write path.

A byte-truncation sweep over a persisted delta chain (every file of the
tail entry, truncated at the start, middle, and last byte) asserts that
restore always lands on the last *intact* entry with the correct
fingerprint; a kill-mid-snapshot test confirms ``.tmp`` wreckage is
ignored and the prior version restores. These are the satellites of the
fsync durability fix in ``repro.ckpt.checkpoint`` — os.rename used to be
the only "commit", which survives a process crash but not a power cut.
The checkpoint module's own round trip, retention and refusals close the
file.
"""
import asyncio
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint
from repro.core import random_graph
from repro.serve import DeltaLog, EngineConfig, LiveIndexService
from repro.core.update import random_delta


def _service(root, **kw):
    kw.setdefault("config", EngineConfig(max_batch=8, flush_ms=2.0))
    kw.setdefault("compact_every", 100)  # keep the whole chain around
    return LiveIndexService(str(root), **kw)


def _build_chain(root, n_deltas=3, n=40, deg=4.0, k=4):
    """Create an index + apply ``n_deltas`` deltas; → {seq: fingerprint}
    (seq 0 = the snapshot) with the chain fully on disk under root."""
    rng = np.random.default_rng(7)
    svc = _service(root)
    fps = {}

    async def main():
        async with svc:
            svc.create("g", random_graph(n, deg, seed=3, weighted=True))
            fps[0] = svc.fingerprint("g")
            for _ in range(n_deltas):
                await svc.apply("g", random_delta(svc.graph("g"), k, rng))
                fps[svc._live["g"].seq] = svc.fingerprint("g")

    asyncio.run(main())
    return fps


def _entry_files(log_dir, seq):
    step = checkpoint.step_dir(log_dir, seq)
    return sorted(os.path.join(step, f) for f in os.listdir(step))


# --------------------------------------------------------------------------
# byte-truncation sweep over the chain tail
# --------------------------------------------------------------------------
def test_truncation_sweep_restores_last_intact_entry(tmp_path):
    """Tear the tail entry at every file boundary and mid-file; recovery
    must always land exactly one entry back, never crash, never serve a
    half-applied delta."""
    fps = _build_chain(tmp_path / "orig", n_deltas=3)
    index_dir = tmp_path / "orig" / "g"
    log = DeltaLog(str(index_dir))
    last = max(log.sequences())
    assert last == 3

    variants = []
    for path in _entry_files(log.directory, last):
        size = os.path.getsize(path)
        # boundary (empty file), mid-entry, and one byte short
        for cut in sorted({0, size // 2, max(size - 1, 0)}):
            variants.append((os.path.basename(path), cut))

    for fname, cut in variants:
        work = tmp_path / f"case_{fname}_{cut}"
        shutil.copytree(index_dir, work / "g")
        wlog = DeltaLog(str(work / "g"))
        victim = os.path.join(checkpoint.step_dir(wlog.directory, last),
                              fname)
        with open(victim, "r+b") as f:
            f.truncate(cut)

        case = f"{fname} truncated at {cut}"
        assert not wlog.verify(last), case
        removed = wlog.truncate_torn_tail()
        assert removed == [last], case
        assert wlog.sequences() == [1, 2], case
        # the surviving tip still carries the fingerprint the writer
        # recorded for it — the restore target is exact, not approximate
        _, tip_fp = wlog.load(2)
        assert tip_fp == fps[2], case


def test_truncated_manifest_is_torn_too(tmp_path):
    """The manifest itself torn (not just an array leaf) must also read
    as a damaged entry, not a parse crash."""
    _build_chain(tmp_path, n_deltas=1)
    log = DeltaLog(str(tmp_path / "g"))
    man = os.path.join(checkpoint.step_dir(log.directory, 1),
                       "manifest.json")
    with open(man, "r+b") as f:
        f.truncate(os.path.getsize(man) // 2)
    assert not log.verify(1)
    assert log.truncate_torn_tail() == [1]


def test_mid_chain_damage_drops_everything_after(tmp_path):
    """A torn entry strands every later entry (they chain off a delta
    that never durably committed): the whole suffix goes."""
    _build_chain(tmp_path, n_deltas=3)
    log = DeltaLog(str(tmp_path / "g"))
    files = _entry_files(log.directory, 2)
    npys = [f for f in files if f.endswith(".npy")]
    with open(npys[0], "r+b") as f:
        f.truncate(1)
    assert log.truncate_torn_tail() == [2, 3]
    assert log.sequences() == [1]


def test_service_restore_after_torn_tail_serves_verified_state(tmp_path):
    """End to end: a service restarted over a torn chain truncates the
    tail (it owns the chain), replays the intact prefix, and serves the
    fingerprint recorded at the surviving tip."""
    fps = _build_chain(tmp_path, n_deltas=2)
    log = DeltaLog(str(tmp_path / "g"))
    files = [f for f in _entry_files(log.directory, 2)
             if f.endswith(".npy")]
    with open(files[-1], "r+b") as f:
        f.truncate(os.path.getsize(files[-1]) // 2)

    svc = _service(tmp_path)

    async def main():
        async with svc:
            fp = svc.load("g")
            res = await svc.query("g", 2, 0.5)
            return fp, svc._live["g"].seq, res

    fp, seq, res = asyncio.run(main())
    assert seq == 1
    assert fp == fps[1]
    assert res.n_clusters >= 0  # it actually serves


# --------------------------------------------------------------------------
# kill mid-snapshot
# --------------------------------------------------------------------------
def test_tmp_wreckage_ignored_and_prior_version_restores(tmp_path):
    """A crash mid-``save`` leaves a ``.tmp`` directory that must be
    invisible to every reader: latest_step skips it, restore serves the
    previous committed version, and the next commit reuses the slot."""
    fps = _build_chain(tmp_path, n_deltas=1)
    store_dir = tmp_path / "g"
    # fake a writer dying halfway through snapshot version 1
    import pathlib
    wreck = pathlib.Path(checkpoint.step_dir(str(store_dir), 1) + ".tmp")
    wreck.mkdir()
    (wreck / "manifest.json").write_text('{"truncated', encoding="utf-8")
    (wreck / "arr_00000.npy").write_bytes(b"\x93NUMPY garbage")

    assert checkpoint.latest_step(str(store_dir)) == 0

    svc = _service(tmp_path)

    async def main():
        async with svc:
            return svc.load("g"), svc._live["g"].seq

    fp, seq = asyncio.run(main())
    assert seq == 1          # snapshot v0 + the one intact chain entry
    assert fp == fps[1]


def test_verify_step_detects_shape_lies(tmp_path):
    """verify_step is byte-level *and* shape-level: a leaf that loads but
    with the wrong shape (swapped files, partial overwrite) fails."""
    tree = {"a": np.arange(6, dtype=np.int64),
            "b": np.zeros((2, 2), dtype=np.float32)}
    checkpoint.save(str(tmp_path), 0, tree)
    assert checkpoint.verify_step(str(tmp_path), 0)
    step = checkpoint.step_dir(str(tmp_path), 0)
    files = sorted(f for f in os.listdir(step) if f.endswith(".npy"))
    # overwrite one leaf with a differently-shaped valid npy
    np.save(os.path.join(step, files[0]), np.arange(2, dtype=np.int64))
    assert not checkpoint.verify_step(str(tmp_path), 0)


def test_fsync_helpers_roundtrip(tmp_path):
    """fsync_file_then_dir is a no-op semantically — contents unchanged,
    durability only — and works on fresh files in fresh directories."""
    p = tmp_path / "sub" / "f.bin"
    p.parent.mkdir()
    p.write_bytes(b"payload")
    checkpoint.fsync_file_then_dir(str(p))
    checkpoint.fsync_dir(str(tmp_path))
    assert p.read_bytes() == b"payload"


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------
def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (16, 8)),
            "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                       "c": jax.random.normal(k, (4,)).astype(jnp.bfloat16)},
            "count": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    checkpoint.save(str(tmp_path), 3, t)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), t)
    out = checkpoint.restore(str(tmp_path), 3, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_latest_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(str(tmp_path), s, t, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000004", "step_00000005"]


def test_checkpoint_no_partial_commit(tmp_path):
    """A stale .tmp directory is never treated as a checkpoint."""
    t = _tree()
    checkpoint.save(str(tmp_path), 1, t)
    os.makedirs(tmp_path / "step_00000002.tmp")
    assert checkpoint.latest_step(str(tmp_path)) == 1


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    checkpoint.save(str(tmp_path), 1, {"a": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        checkpoint.restore(str(tmp_path), 1, {"a": jnp.zeros((5,))})
