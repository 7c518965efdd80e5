"""Pallas TPU kernel: per-edge sketch comparison (XOR + popcount).

Consumes packed SimHash sketches gathered to edge endpoints and emits the
integer Hamming distance per edge:

    diff = Σ_w popcount(sk_u[w] XOR sk_v[w])

The σ̂ epilogue, cos(π · diff / k), is :func:`hamming_sigma`: shared jnp
code that every lane runs on the same integer distances, so the lanes can
never disagree on it (the exact-σ engine's ``_sigma_epilogue`` follows the
same rule).

The words are bitcast to int32 and the popcount is branch-free SWAR
arithmetic with logical shifts and adds — plain VPU integer ops, no
unsigned reduction and no native population-count instruction. Edges lie
on the 128-wide lane axis and the ``words`` of a sketch down the sublanes,
so the word sum is a sublane reduction that lands as a lane-dense
``(1, be)`` output row. One grid dimension over edge blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _popcount_i32(x: jax.Array) -> jax.Array:
    """SWAR popcount of each int32 word's 32 bits, int32 → int32."""
    srl = lax.shift_right_logical
    x = x - (srl(x, 1) & 0x55555555)
    x = (x & 0x33333333) + (srl(x, 2) & 0x33333333)
    x = (x + srl(x, 4)) & 0x0F0F0F0F
    x = x + srl(x, 8)
    x = x + srl(x, 16)
    return x & 0x3F


def _kernel(su_ref, sv_ref, o_ref):
    x = jnp.bitwise_xor(su_ref[...], sv_ref[...])
    o_ref[...] = jnp.sum(_popcount_i32(x), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("be", "interpret"))
def hamming_distance(
    sk_u: jax.Array,   # uint32[e, words] sketches gathered at edge sources
    sk_v: jax.Array,   # uint32[e, words] sketches gathered at edge targets
    *,
    be: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Differing sketch bits per edge, int32[e]. e must be a multiple of
    be; on the chip be is a multiple of 128 (or all of e)."""
    e, words = sk_u.shape
    assert sk_v.shape == (e, words)
    assert e % be == 0, "pad edge count to a block multiple"
    su = lax.bitcast_convert_type(sk_u, jnp.int32).T
    sv = lax.bitcast_convert_type(sk_v, jnp.int32).T
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        grid=(e // be,),
        in_specs=[
            pl.BlockSpec((words, be), lambda i: (0, i)),
            pl.BlockSpec((words, be), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, be), lambda i: (0, i)),
        interpret=interpret,
    )(su, sv)
    return out[0]


@functools.partial(jax.jit, static_argnames=("samples",))
def hamming_sigma(diff: jax.Array, samples: int) -> jax.Array:
    """σ̂ = cos(π · diff / k) per edge, float32 — the one epilogue every
    ``hamming`` lane shares. Jitted, so it lowers through one compiler path
    whichever lane produced ``diff``."""
    return jnp.cos(jnp.pi * diff.astype(jnp.float32) / samples)
