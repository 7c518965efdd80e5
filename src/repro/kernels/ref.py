"""Pure-jnp oracles for every Pallas kernel. Tests assert allclose."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def masked_gram_ref(w: jax.Array, mask: jax.Array) -> jax.Array:
    return (w @ w.T) * mask


def bucket_probe_ref(p_ids, p_w, t_ids, t_w):
    """(shared dot, shared count) per edge row-pair; pad ids must differ
    between probe (-1) and target (-2) so padding never matches."""
    eq = p_ids[:, :, None] == t_ids[:, None, :]
    w = p_w[:, :, None] * t_w[:, None, :]
    dot = jnp.sum(jnp.where(eq, w, 0.0), axis=(1, 2))
    cnt = jnp.sum(eq, axis=(1, 2)).astype(jnp.int32)
    return dot, cnt


def simhash_pack_ref(w: jax.Array, r: jax.Array) -> jax.Array:
    s = w @ r
    bits = (s >= 0.0).astype(jnp.uint32)
    n, k = bits.shape
    lanes = bits.reshape(n, k // 32, 32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(lanes * weights[None, None, :], axis=-1, dtype=jnp.uint32)


def hamming_distance_ref(sk_u: jax.Array, sk_v: jax.Array) -> jax.Array:
    """Differing bits per row of packed uint32 sketches, int32[e]."""
    x = jnp.bitwise_xor(sk_u, sk_v)
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
