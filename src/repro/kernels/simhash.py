"""Pallas TPU kernel: SimHash sketch construction (paper §5).

sketch(v) = sign(W̄c[v, :] · R) for R ∈ ℝ^{n×k} i.i.d. N(0,1): the kn dot
products are one matmul — the MXU path. The kernel fuses the sign and packs
32 sample bits per 32-bit word *before* the HBM write-back, cutting sketch
bandwidth 32× (sketches are re-read once per edge by the hamming kernel, so
the packing pays on both sides).

Grid (n/bm, n/bk): the contraction over the vertex axis (bk) is the inner
loop accumulating into a VMEM scratch tile of all k samples; the last step
packs. Packing is two more small matmuls: the 0/1 sign bits times a
(k, k/32) matrix of powers of two, once for bit positions 0–15 and once
for 16–31, so every partial word is an integer below 2¹⁶ (exact in f32 and
in bf16 operands), then ``hi << 16 | lo`` in int32. The output block holds
all k/32 words of a row, so its last dimension is the array's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_weights(k: int) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) float32[k, k/32]: column w holds 2^(b mod 32) at the rows
    b of word w, split at bit 16 so each partial word stays below 2¹⁶."""
    b = np.arange(k)
    bit = b % 32
    onehot = (b[:, None] // 32) == np.arange(k // 32)[None, :]
    lo = np.where(onehot & (bit < 16)[:, None], 2.0 ** (bit % 16)[:, None], 0)
    hi = np.where(onehot & (bit >= 16)[:, None], 2.0 ** (bit % 16)[:, None], 0)
    return lo.astype(np.float32), hi.astype(np.float32)


def _kernel(w_ref, r_ref, lo_ref, hi_ref, o_ref, acc_ref, *, nk: int):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        w_ref[...], r_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(kk == nk - 1)
    def _pack():
        bits = (acc_ref[...] >= 0.0).astype(jnp.float32)      # [bm, k]
        lo = jnp.dot(bits, lo_ref[...], preferred_element_type=jnp.float32)
        hi = jnp.dot(bits, hi_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def simhash_pack(
    w: jax.Array,   # float32[n, n] closed weighted adjacency (padded)
    r: jax.Array,   # float32[n, k] gaussian projections (k multiple of 32)
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """Packed sketches uint32[n, k/32]."""
    n, k = w.shape[0], r.shape[1]
    assert w.shape == (n, n) and r.shape[0] == n
    assert n % bm == 0 and n % bk == 0 and k % 32 == 0
    nk = n // bk
    lo, hi = _pack_weights(k)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((n, k // 32), jnp.int32),
        grid=(n // bm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
            pl.BlockSpec((bk, k), lambda i, kk: (kk, 0)),
            pl.BlockSpec((k, k // 32), lambda i, kk: (0, 0)),
            pl.BlockSpec((k, k // 32), lambda i, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k // 32), lambda i, kk: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, r, jnp.asarray(lo), jnp.asarray(hi))
    return lax.bitcast_convert_type(out, jnp.uint32)
