"""Pallas TPU kernel: degree-bucketed shared-neighbor probe.

The bucketed similarity engine (repro.core.similarity) routes each edge to
a fixed-shape (probe class, target class) kernel: the min-degree side's
sorted row is matched against the max-degree side's sorted row. On TPU the
heaviest classes run this kernel instead of the jnp searchsorted path.

Per edge ``e`` the kernel computes

    dot[e]  = Σ_i Σ_j [p_ids[e,i] == t_ids[e,j]] · p_w[e,i] · t_w[e,j]
    cnt[e]  = Σ_i Σ_j [p_ids[e,i] == t_ids[e,j]]

**Layout: edges on lanes.** The operands are transposed so that the edge
axis is the 128-wide lane axis and row positions run down the sublanes. A
probe position ``i`` is then one ``(1, be)`` row that broadcasts over
sublanes against a ``(sub, be)`` slab of target positions: every compare,
select and add is a full-width 2-D vector op, there is no 3-D
intermediate, and the per-edge sums come out of a sublane reduction as a
lane-dense ``(1, be)`` row — the output block layout.

**Tiling: probe and target both stream.** The grid is
``(edge blocks, probe chunks, target tiles)``; one step holds a
``(pc, be)`` probe chunk and a ``(bt, be)`` target tile in VMEM, so the
fast-memory working set is fixed by the blocks, never by the row widths:
a hub row 2048·2^k wide flows through in ``bt``-row tiles, and a wide
probe row in ``pc``-row chunks. The outputs stay resident across the two
inner (reduction) axes.

Graphs are simple, so each target id is hit by at most one probe id per
edge: the per-position hit weight is a single ``p_w`` (no sum), and the
weighted dot is one product per hit. Padding must be pre-sanitized by the
caller: probe pad ids < 0 and target pad ids < 0 with **different** values
(e.g. -1 / -2) so padding never matches padding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# target positions per inner slab: a few vregs tall, so the slab and its
# two hit accumulators stay in registers while the probe chunk streams by
_SUB = 32
_UNROLL = 8


def _kernel(p_ids_ref, p_w_ref, t_ids_ref, t_w_ref, dot_ref, cnt_ref, *,
            sub: int):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    pc, be = p_ids_ref.shape
    bt = t_ids_ref.shape[0]
    unroll = math.gcd(pc, _UNROLL)

    def target_slab(r, acc):
        dot, cnt = acc
        r0 = pl.multiple_of(r * sub, sub)
        t_ids = t_ids_ref[pl.ds(r0, sub), :]

        def probe_rows(i, hits):
            # ``unroll`` probe rows per trip (Mosaic loops unroll fully or
            # not at all, so the body unrolls by hand)
            w_hit, c_hit = hits
            i0 = pl.multiple_of(i * unroll, unroll)
            for u in range(unroll):
                eq = t_ids == p_ids_ref[pl.ds(i0 + u, 1), :]
                w_hit = w_hit + jnp.where(
                    eq, p_w_ref[pl.ds(i0 + u, 1), :], 0.0)
                c_hit = c_hit + eq.astype(jnp.int32)
            return w_hit, c_hit

        w_hit, c_hit = lax.fori_loop(
            0, pc // unroll, probe_rows,
            (jnp.zeros((sub, be), jnp.float32),
             jnp.zeros((sub, be), jnp.int32)))
        w_t = t_w_ref[pl.ds(r0, sub), :]
        return (dot + jnp.sum(w_hit * w_t, axis=0, keepdims=True),
                cnt + jnp.sum(c_hit, axis=0, keepdims=True))

    dot, cnt = lax.fori_loop(
        0, bt // sub, target_slab,
        (jnp.zeros((1, be), jnp.float32), jnp.zeros((1, be), jnp.int32)))
    dot_ref[...] += dot
    cnt_ref[...] += cnt


@functools.partial(
    jax.jit, static_argnames=("be", "bt", "interpret")
)
def bucket_probe(
    p_ids: jax.Array,   # int32[e, P]   probe rows (pad id -1)
    p_w: jax.Array,     # float32[e, P]
    t_ids: jax.Array,   # int32[e, T]   target rows (pad id -2)
    t_w: jax.Array,     # float32[e, T]
    *,
    be: int = 256,
    bt: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(shared weighted dot float32[e], shared count int32[e]).

    ``e`` must be a multiple of ``be`` and ``T`` of ``bt``; the probe width
    streams in chunks of ``min(P, bt)`` positions, so ``P`` must be a
    multiple of that. The caller pads (repro.kernels.ops.probe_operands
    does). On the chip ``be`` is a multiple of 128 (or all of ``e``) and
    ``bt`` a multiple of 8 (or all of ``T``)."""
    e, p = p_ids.shape
    t = t_ids.shape[1]
    assert p_w.shape == (e, p) and t_ids.shape == (e, t) \
        and t_w.shape == (e, t)
    assert e % be == 0, "pad edge count to a block multiple"
    assert t % bt == 0, "pad target width to a tile multiple"
    pc = min(p, bt)
    assert p % pc == 0, "pad probe width to a chunk multiple"
    sub = math.gcd(bt, _SUB)
    dot, cnt = pl.pallas_call(
        functools.partial(_kernel, sub=sub),
        out_shape=(
            jax.ShapeDtypeStruct((1, e), jnp.float32),
            jax.ShapeDtypeStruct((1, e), jnp.int32),
        ),
        grid=(e // be, p // pc, t // bt),
        in_specs=[
            pl.BlockSpec((pc, be), lambda i, k, j: (k, i)),   # probe chunk
            pl.BlockSpec((pc, be), lambda i, k, j: (k, i)),
            pl.BlockSpec((bt, be), lambda i, k, j: (j, i)),   # target tile
            pl.BlockSpec((bt, be), lambda i, k, j: (j, i)),
        ],
        out_specs=(
            pl.BlockSpec((1, be), lambda i, k, j: (0, i)),
            pl.BlockSpec((1, be), lambda i, k, j: (0, i)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(p_ids.T, p_w.T, t_ids.T, t_w.T)
    return dot[0], cnt[0]
