"""Parallel connectivity via min-label propagation + pointer jumping.

The paper uses Gazit's O(log n)-span connectivity (theory) and concurrent
union-find (implementation, §6.2). Neither CAS-loops nor work-stealing exist
on TPU, so we use the standard vector-parallel equivalent: every vertex
carries a label (initialized to its own id); each round takes the min of
the neighbor labels across the active edge set, hooks the vertex each
label points at onto the same minimum (Shiloach–Vishkin hooking), then
pointer-jumps (``labels = labels[labels]``, twice) to compress chains.
Each round is a constant number of bulk passes → O(log n) rounds w.h.p.
on real graphs, matching the span target; a ``while_loop`` on the
changed-flag guarantees exact convergence regardless. The fixed point —
every label the smallest vertex id of its component — is unique, so the
order of the updates never changes the answer.

The per-vertex min is a reduction over the vertex's CSR row, not a
scatter: half-edges are stored row by row, so :func:`row_reduce` takes a
segmented prefix scan (``steps`` doubling passes, 2**steps ≥ the longest
row) and reads each row's last slot. On a TPU a scatter-min over millions
of half-edges serializes (one such round took 4.1 s for 32 lanes at
16.6M half-edges on a v5e, the scan 0.7 s); the scan is elementwise work
over contiguous slots. Only the hooking step scatters, over n slots.

With ``axis_name`` the same loop runs with the edge set *sharded* over a
mesh axis (inside ``shard_map``): each shard reduces its local slots (a
row cut by a shard boundary contributes a partial min from each side) and
an ``lax.pmin`` all-reduce merges the proposals. min is associative, so
the merged proposal equals the single-device reduction over the full edge
set and the per-round label sequence — hence the fixed point — is
bit-identical to the unsharded path. The changed-flag is computed from
replicated state, so every shard exits the while_loop on the same round.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RowLayout(NamedTuple):
    """Where each half-edge slot sits in its CSR row.

    ``pos`` int32[E]: slot index within its row (distance to the row's
    first slot held by this array); ``last`` int32[n]: each vertex's last
    slot, −1 for a vertex with no slot; ``steps``: doubling passes, with
    2**steps ≥ the longest row (static)."""

    pos: jax.Array
    last: jax.Array
    steps: int


def row_steps(max_row: int) -> int:
    """Doubling passes that cover a row of ``max_row`` slots."""
    return max(int(max_row) - 1, 0).bit_length()


def row_layout(offsets: jax.Array, edge_u: jax.Array, max_row: int
               ) -> RowLayout:
    """:class:`RowLayout` of a CSR graph's half-edges (``max_row`` bounds
    the degree)."""
    e = jnp.arange(edge_u.shape[0], dtype=jnp.int32)
    pos = e - offsets[edge_u]
    last = jnp.where(offsets[1:] > offsets[:-1], offsets[1:] - 1, -1)
    return RowLayout(pos, last.astype(jnp.int32), row_steps(max_row))


def row_reduce(vals: jax.Array, rows: RowLayout, op, fill) -> jax.Array:
    """Per-vertex ``op``-reduction (an exact min or max) of ``vals``
    [E] over each vertex's row; ``fill`` for a vertex with no slot."""
    e = vals.shape[0]
    n = rows.last.shape[0]
    if e == 0:
        return jnp.full((n,), fill, vals.dtype)
    x = vals
    if rows.steps:
        width = 1 << (rows.steps - 1)
        pad = jnp.full((width,), fill, vals.dtype)

        def body(k, x):
            # inclusive segmented scan (Hillis–Steele): slot i takes slot
            # i − 2**k while both sit in the same row
            s = jnp.left_shift(jnp.int32(1), k)
            shifted = jax.lax.dynamic_slice(
                jnp.concatenate([pad, x]), (width - s,), (e,))
            return jnp.where(rows.pos >= s, op(x, shifted), x)

        x = jax.lax.fori_loop(0, rows.steps, body, x)
    return jnp.where(rows.last >= 0, x[jnp.maximum(rows.last, 0)],
                     jnp.asarray(fill, vals.dtype))


def connected_components(
    n: int,
    ev: jax.Array,         # int32[E] neighbor endpoint of each slot
    edge_mask: jax.Array,  # bool[E] active half-edges
    rows: RowLayout,       # the slots' rows (the other endpoint)
    vertex_mask: jax.Array | None = None,  # bool[n] active vertices
    axis_name: str | None = None,  # set inside shard_map: slots are a shard
) -> tuple[jax.Array, jax.Array]:
    """(labels int32[n], rounds int32): labels are the min vertex id of the
    component (only meaningful where vertex_mask; inactive vertices keep
    label = own id); rounds counts the propagation rounds run, the last
    one the round that found nothing to change. Under ``vmap`` each lane
    counts its own rounds, while the batched loop runs the most any lane
    needs."""
    if vertex_mask is None:
        vertex_mask = jnp.ones((n,), dtype=bool)

    init = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(n)

    def body(state):
        labels, _, rounds = state
        with jax.named_scope("query.propagate"):
            lv = jnp.where(edge_mask, labels[ev], big)
            # min neighbor label per row (shard-local when sharded)
            prop = row_reduce(lv, rows, jnp.minimum, big)
            if axis_name is not None:
                prop = jax.lax.pmin(prop, axis_name)
            new = jnp.where(vertex_mask, jnp.minimum(labels, prop), labels)
            # hooking: the vertex each label points at (same component)
            # takes the proposal too, so whole trees merge in one round
            # instead of labels crossing one edge per round (75 rounds → 7
            # on a 2¹⁷ power-law graph). n scattered slots, not m2.
            hook = jnp.where(vertex_mask & (prop < big), labels, big)
            new = new.at[hook].min(prop, mode="drop")
            # pointer jumping (path compression) — twice per round
            new = new[new]
            new = new[new]
            changed = jnp.any(new != labels)
        return new, changed, rounds + 1

    def cond(state):
        return state[1]

    labels, _, rounds = jax.lax.while_loop(
        cond, body, (init, jnp.bool_(True), jnp.int32(0)))
    return labels, rounds
