"""Shared padding / power-of-two shape helpers.

Every fixed-shape trick in the repo — pow2 degree classes, pow2 chunk
buckets, block-multiple kernel operands — reduces to the same handful of
helpers. They used to live twice (``_pad_to`` in ``kernels/ops.py``,
``_pow2ceil``/``_pow2_bucket``/``_pad1`` in ``core/similarity.py``); this
module is the single home. ``core.similarity`` re-exports them under the
old underscore names for back-compat.

All helpers are shape-static (padding amounts derive from ``.shape``), so
the jnp ones are safe inside ``jax.jit`` traces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def pow2ceil(x: int, floor: int = 1) -> int:
    """Smallest power of two ≥ max(x, floor)."""
    v = max(int(x), floor, 1)
    return 1 << (v - 1).bit_length()


def pow2_bucket(total: int, floor: int = 64) -> int:
    """Smallest power-of-two ≥ ``total`` (≥ ``floor``) — the fixed chunk
    shapes that let repeated subset passes share compiled kernels."""
    b = floor
    while b < total:
        b <<= 1
    return b


def np_pow2ceil(x: np.ndarray) -> np.ndarray:
    """Elementwise :func:`pow2ceil` (floor 1), int64."""
    x = np.maximum(np.asarray(x, np.int64), 1)
    return 1 << np.ceil(np.log2(x)).astype(np.int64)


def np_log2(x: np.ndarray) -> np.ndarray:
    """Elementwise exact log2 of power-of-two int arrays, int64."""
    return np.log2(np.asarray(x, np.int64)).astype(np.int64)


def pad1(a: np.ndarray, pad: int, fill) -> np.ndarray:
    """Append ``pad`` copies of ``fill`` to a 1-d numpy array."""
    if pad == 0:
        return a
    return np.concatenate([a, np.full(pad, fill, dtype=a.dtype)])


def pad_to(x: jax.Array, mult: int, axes, value=0) -> jax.Array:
    """Pad ``axes`` of ``x`` with ``value`` (default zero) up to the next
    multiple of ``mult``."""
    pads = [(0, 0)] * x.ndim
    for ax in axes:
        rem = (-x.shape[ax]) % mult
        pads[ax] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)
