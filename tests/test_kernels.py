"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.kernels import ops
from repro.kernels.bucket_probe import bucket_probe
from repro.kernels.triangle_count import masked_gram
from repro.kernels.simhash import simhash_pack
from repro.kernels.hamming import hamming_distance, hamming_sigma

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,block", [(128, 128), (256, 128), (256, 64),
                                     (384, 128)])
def test_masked_gram_sweep(n, block):
    w = RNG.standard_normal((n, n)).astype(np.float32)
    m = (RNG.random((n, n)) < 0.15).astype(np.float32)
    out = masked_gram(jnp.asarray(w), jnp.asarray(m), bm=block, bn=block,
                      bk=block, interpret=True)
    want = kref.masked_gram_ref(jnp.asarray(w), jnp.asarray(m))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-3)


@pytest.mark.parametrize("e,p,t,be,bt", [(64, 8, 32, 32, 16),
                                         (128, 16, 64, 64, 64),
                                         (32, 8, 128, 32, 32)])
def test_bucket_probe_sweep(e, p, t, be, bt):
    """Degree-bucketed probe kernel vs the pure-jnp oracle, including the
    tiled target axis (t > bt streams the row through multiple grid steps,
    the hub-row splitting path)."""
    n_ids = 64
    ids_p = np.sort(RNG.choice(n_ids, size=(e, p)), axis=1).astype(np.int32)
    ids_t = np.sort(RNG.choice(n_ids, size=(e, t)), axis=1).astype(np.int32)
    # sanitize duplicates away (simple-graph invariant) and pad some tails
    for row in (ids_p, ids_t):
        for i in range(e):
            u = np.unique(row[i])
            pad = -1 if row is ids_p else -2
            row[i] = np.concatenate(
                [u, np.full(row.shape[1] - len(u), pad, np.int32)]
            ) if len(u) < row.shape[1] else row[i]
    w_p = RNG.uniform(0.1, 1.0, size=(e, p)).astype(np.float32)
    w_t = RNG.uniform(0.1, 1.0, size=(e, t)).astype(np.float32)
    dot, cnt = bucket_probe(jnp.asarray(ids_p), jnp.asarray(w_p),
                            jnp.asarray(ids_t), jnp.asarray(w_t),
                            be=be, bt=bt, interpret=True)
    want_dot, want_cnt = kref.bucket_probe_ref(
        jnp.asarray(ids_p), jnp.asarray(w_p),
        jnp.asarray(ids_t), jnp.asarray(w_t))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(want_cnt))
    np.testing.assert_allclose(np.asarray(dot), np.asarray(want_dot),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,k", [(128, 128), (256, 256), (128, 384)])
def test_simhash_pack_sweep(n, k):
    w = RNG.standard_normal((n, n)).astype(np.float32)
    r = RNG.standard_normal((n, k)).astype(np.float32)
    out = simhash_pack(jnp.asarray(w), jnp.asarray(r), interpret=True)
    want = kref.simhash_pack_ref(jnp.asarray(w), jnp.asarray(r))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("e,words,k", [(1024, 4, 128), (2048, 8, 256),
                                       (1024, 1, 32)])
def test_hamming_sweep(e, words, k):
    su = RNG.integers(0, 2**32, size=(e, words), dtype=np.uint32)
    sv = RNG.integers(0, 2**32, size=(e, words), dtype=np.uint32)
    diff = hamming_distance(jnp.asarray(su), jnp.asarray(sv), be=512,
                            interpret=True)
    want = kref.hamming_distance_ref(jnp.asarray(su), jnp.asarray(sv))
    np.testing.assert_array_equal(np.asarray(diff), np.asarray(want))
    # the shared epilogue: σ̂ = cos(π·d/k), identical whichever lane made d
    np.testing.assert_array_equal(
        np.asarray(hamming_sigma(diff, k)), np.asarray(hamming_sigma(want, k)))
    sigma = np.asarray(hamming_sigma(diff, k))
    np.testing.assert_allclose(sigma, np.cos(np.pi * np.asarray(want) / k),
                               atol=1e-6)


def test_kernel_simhash_statistically_sound():
    """Kernel-produced sketches estimate cosine within O(1/√k)."""
    from repro.core import random_graph, compute_similarities
    g = random_graph(200, 8.0, seed=31)
    k = 512
    sk = ops.simhash_sketches_kernel(g, k, jax.random.PRNGKey(0))
    est = np.asarray(ops.simhash_edge_similarity_kernel(
        sk, g.edge_u, g.nbrs, k))
    exact = np.asarray(compute_similarities(g, "cosine"))
    assert np.mean(np.abs(est - exact)) < 0.06
