"""Explicit expert parallelism (shard_map) for the MoE FFN.

The pjit baseline in ``models/moe.py`` scatters data-sharded tokens into an
expert-sharded ``[E, capacity, d]`` buffer and lets XLA pick the
collectives. This module is the explicit variant: a ``shard_map`` over the
(data, model) mesh where every device

  1. all-gathers the token shard over the data axes (routing is replicated
     math — identical top-k and capacity positions on every device, so no
     f32 cotangent crosses the shard boundary);
  2. builds the dispatch buffer *only for its local experts* (the ``model``
     axis owns ``E / tp`` experts each) and runs the three expert einsums;
  3. psum-combines the weighted expert outputs over the ``model`` axis
     (each (token, slot) lives on exactly one expert shard; dropped slots
     contribute zero everywhere) and slices its own token rows back out.

Capacity, ordering and renormalized router weights are computed from the
*global* token count, so outputs match the pjit baseline to float tolerance.

The mesh is process-global state (``set_ep_mesh``) because the config that
selects ``moe_impl="ep"`` is a frozen dataclass threaded through jit — the
mesh handle cannot ride along as a traced value.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_EP_MESH = None
_EP_DP_AXES: Tuple[str, ...] = ()
_EP_AXIS: str = "model"


def set_ep_mesh(mesh, dp_axes: Optional[Tuple[str, ...]] = None,
                ep_axis: str = "model") -> None:
    """Install (or clear, with ``mesh=None``) the EP mesh."""
    global _EP_MESH, _EP_DP_AXES, _EP_AXIS
    _EP_MESH = mesh
    _EP_DP_AXES = tuple(dp_axes) if dp_axes else ()
    _EP_AXIS = ep_axis


def ep_enabled() -> bool:
    return _EP_MESH is not None


def ep_ffn(xf, router, w_gate, w_up, w_down, cfg):
    """Expert-parallel routed FFN. ``xf``: [T, d] (data-sharded), expert
    weights [E, ...] (sharded over the EP axis). Returns [T, d]."""
    mesh, dp_axes, ep_axis = _EP_MESH, _EP_DP_AXES, _EP_AXIS
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(int(cfg.capacity_factor * t * k / e), 1)
    dp_entry = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    def shard_fn(x_l, router_l, wg, wu, wd):
        # ---- replicate tokens within the expert group ----
        x_g = x_l
        for a in reversed(dp_axes):          # inner-most axis first
            x_g = jax.lax.all_gather(x_g, a, axis=0, tiled=True)

        # ---- routing (replicated math, same as the pjit baseline) ----
        logits = (x_g @ router_l.astype(x_g.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        flat_e = top_i.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.cumsum(counts) - counts
        pos = (jnp.arange(t * k, dtype=jnp.int32)
               - starts[sorted_e].astype(jnp.int32))
        keep = pos < cap

        # ---- local experts only ----
        e_l = wg.shape[0]
        e0 = jax.lax.axis_index(ep_axis) * e_l
        local = keep & (sorted_e >= e0) & (sorted_e < e0 + e_l)
        dest = jnp.where(local, (sorted_e - e0) * cap + pos, e_l * cap)
        src_token = order // k
        buf = jnp.zeros((e_l * cap, d), x_g.dtype).at[dest].set(
            x_g[src_token], mode="drop")
        h = buf.reshape(e_l, cap, d)
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg))
        act = act * jnp.einsum("ecd,edf->ecf", h, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", act, wd).reshape(e_l * cap, d)

        dest_of_slot = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.where(local, dest, e_l * cap).astype(jnp.int32))
        padded = jnp.concatenate(
            [out_buf, jnp.zeros((1, d), x_g.dtype)], axis=0)
        expert_out = padded[dest_of_slot].reshape(t, k, d)
        combined = jnp.sum(
            expert_out * top_p[..., None].astype(x_g.dtype), axis=1)
        combined = jax.lax.psum(combined, ep_axis)

        # ---- back to this device's token rows ----
        idx = 0
        for a in dp_axes:                    # outer-major linear index
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        t_l = x_l.shape[0]
        return jax.lax.dynamic_slice_in_dim(combined, idx * t_l, t_l, axis=0)

    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(dp_entry, None), P(None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=P(dp_entry, None),
        check_vma=False,
    )(xf, router, w_gate, w_up, w_down)
