"""Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell:
  lower the step function (train_step / prefill / decode_step) with
  ShapeDtypeStruct inputs under the production mesh, .compile() it, and
  record memory_analysis / cost_analysis / HLO collective stats as a JSON
  artifact for EXPERIMENTS.md §Dry-run and benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--cells N,M]
Artifacts: experiments/dryrun/<mesh>/<arch>__<shape>.json
"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_EXTRA", "") +
    " --xla_force_host_platform_device_count=512"
)
# ^ MUST precede every other import (jax locks device count on first init).

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, all_arch_ids
from repro.configs.shapes import SHAPES, cell_runs
from repro.dist.sharding import Sharder
from repro.launch.collectives import parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.models import model as mdl
from repro.models import layers as Lyr
from repro.optim import adamw
from repro.train.train_step import make_train_step

# --- TPU v5e hardware constants (roofline) ---
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link (1-link conservative model)

# per-arch microbatch accumulation for train_4k (global batch 256);
# clamped so each microbatch still covers the DP axis.
ACCUM = {
    "whisper-small": 2, "pixtral-12b": 8, "granite-20b": 8, "yi-34b": 16,
    "granite-34b": 16, "granite-8b": 4, "mamba2-780m": 2,
    "deepseek-v2-lite-16b": 4, "moonshot-v1-16b-a3b": 4, "hymba-1.5b": 2,
}


def build_cell(arch: str, shape_name: str, mesh, *, overrides=None,
               sp: bool = False, accum_override=None):
    """→ (lowered, meta) for one cell, or raises."""
    from jax.sharding import PartitionSpec as P

    cfg = get_config(arch)
    over = dict(overrides or {})
    # serving cells need the chunked attention path for 32k; training too
    over.setdefault("attn_impl", "chunked")
    over.setdefault("q_chunk", 4096)
    cfg = cfg.scaled(**over)
    shape = SHAPES[shape_name]
    sharder = Sharder(mesh, cfg)
    dp = sharder.dp
    dp_axes = sharder.dp_axes if len(sharder.dp_axes) > 1 else sharder.dp_axes[0]

    if sp:
        Lyr.set_sp_spec(P(dp_axes, "model", None))
    else:
        Lyr.set_sp_spec(None)
    Lyr.set_softmax_dtype(jnp.bfloat16 if cfg.softmax_dtype == "bf16"
                          else jnp.float32)
    from repro.dist import ep as ep_mod
    if cfg.moe_impl == "ep":
        ep_mod.set_ep_mesh(mesh, sharder.dp_axes, "model")
    else:
        ep_mod.set_ep_mesh(None)

    param_shapes = jax.eval_shape(
        lambda k: mdl.init_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    pspecs = sharder.param_specs(param_shapes)
    pshard = sharder.tree_named(pspecs)

    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "n_params": int(sum(np.prod(l.shape) for l in jax.tree.leaves(param_shapes))),
            "n_active_params": mdl.count_active_params(cfg)}

    if shape.kind == "train":
        # ACCUM holds the deployment values (memory-fit); the dry-run uses
        # accum=1 — roofline terms are accum-invariant (same global math)
        # and compile time scales with the unrolled microstep count.
        accum = accum_override or 1
        accum = max(1, min(accum, shape.global_batch // dp))
        micro = shape.global_batch // accum
        meta["accum"] = accum
        specs = mdl.input_specs(cfg, shape)["batch"]
        batch_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((accum, micro) + tuple(s.shape[1:]),
                                           s.dtype), specs)
        bspecs = sharder.batch_specs(batch_shapes, leading_accum=True)
        bshard = sharder.tree_named(bspecs)
        opt_shapes = jax.eval_shape(adamw.init, param_shapes)
        ospecs = sharder.opt_specs(pspecs, param_shapes)
        oshard = sharder.tree_named(ospecs)
        hp = adamw.AdamWConfig()
        step = make_train_step(cfg, hp, accum=accum)
        jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(param_shapes, opt_shapes, batch_shapes)

    elif shape.kind == "prefill":
        specs = mdl.input_specs(cfg, shape)
        batch_shapes = specs["batch"]
        bspecs = sharder.batch_specs(batch_shapes)
        bshard = sharder.tree_named(bspecs)
        cache_shapes = jax.eval_shape(lambda: mdl.init_cache(
            cfg, shape.global_batch, shape.seq_len))
        cspecs = sharder.cache_specs(cache_shapes, kind="prefill")
        cshard = sharder.tree_named(cspecs)

        def prefill_fn(params, batch):
            return mdl.prefill(cfg, params, batch, shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(pshard, bshard),
                         out_shardings=(None, cshard))
        lowered = jitted.lower(param_shapes, batch_shapes)

    elif shape.kind == "decode":
        specs = mdl.input_specs(cfg, shape)
        cache_shapes = specs["cache"]
        cspecs = sharder.cache_specs(cache_shapes)
        cshard = sharder.tree_named(cspecs)
        tshard = sharder.named(sharder.batch_specs(
            {"t": specs["token"]})["t"])
        pos = mdl.decode_pos(cfg, shape)

        def decode_fn(params, cache, token, pos_):
            return mdl.decode_step(cfg, params, cache, token, pos_)

        jitted = jax.jit(decode_fn,
                         in_shardings=(pshard, cshard, tshard, None),
                         out_shardings=(None, cshard),
                         donate_argnums=(1,))
        lowered = jitted.lower(param_shapes, cache_shapes, specs["token"],
                               jax.ShapeDtypeStruct((), jnp.int32))
        meta["decode_pos"] = pos
    else:
        raise ValueError(shape.kind)

    Lyr.set_sp_spec(None)
    return lowered, meta


def analytic_memory(cfg, shape, meta, n_dev: int, dp: int, tp: int) -> dict:
    """Analytic per-device HBM estimates (the tight counterpart to the
    HLO 'bytes accessed' upper bound — CPU-backend buffer accounting ignores
    remat/serialization, so both bounds are reported; see EXPERIMENTS.md).

    * weights_GiB: persistent param bytes per device (TP-sharded big tensors)
    * opt_GiB:     fp32 m/v/master, ZeRO-sharded over the full mesh
    * act_peak_GiB: live activations with per-layer remat + chunked attention
    * traffic_GiB: minimum HBM traffic per step (params + residual r/w)
    """
    n = meta["n_params"]
    bytes_params = 2 * n / min(tp, 16)      # bf16, TP-sharded (approx)
    kind = shape.kind
    accum = meta.get("accum", 1)
    b_dev = max(shape.global_batch // max(accum, 1) // dp, 1)
    s = shape.seq_len
    d = cfg.d_model
    layers = cfg.n_layers + cfg.n_enc_layers
    if kind == "train":
        opt = 12 * n / n_dev                # ZeRO-1 over full mesh
        resid = layers * b_dev * s * d * 2  # saved layer inputs (remat)
        chunk_peak = 4 * b_dev * max(cfg.n_heads // tp, 1) * cfg.q_chunk * s
        logits = 4 * b_dev * s * cfg.vocab_padded / tp
        act = resid + chunk_peak + logits
        traffic = 3 * bytes_params + 2 * opt + 4 * resid
    elif kind == "prefill":
        opt = 0
        act = 2 * b_dev * s * d * 4 + 4 * b_dev * max(
            cfg.n_heads // tp, 1) * cfg.q_chunk * s
        traffic = bytes_params + 2 * act
    else:  # decode
        opt = 0
        act = b_dev * d * 4 * layers
        cache = meta.get("cache_bytes_dev", 0.0)
        traffic = bytes_params + cache
    return {
        "weights_GiB": bytes_params / 2**30,
        "opt_GiB": opt / 2**30,
        "act_peak_GiB": act / 2**30,
        "min_traffic_GiB": traffic / 2**30,
        "min_memory_s": traffic / HBM_BW,
    }


def model_flops(meta, shape) -> float:
    """Analytic MODEL_FLOPS for the cell (global, per step)."""
    n = meta["n_active_params"]
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch      # decode: one token per row


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             *, sp=False, overrides=None, accum_override=None,
             tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    runs, reason = cell_runs(cfg.family, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skip", "reason": reason}
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name,
                        f"{arch}__{shape_name}{tag}.json")
    if not runs:
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[dryrun] SKIP {arch} {shape_name}: {reason}", flush=True)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
        with mesh:
            lowered, meta = build_cell(arch, shape_name, mesh, sp=sp,
                                       overrides=overrides,
                                       accum_override=accum_override)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ca = compiled.cost_analysis() or {}
            ma = compiled.memory_analysis()
            hlo = compiled.as_text()
            coll = parse_collectives(hlo)
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=repr(e),
                   trace=traceback.format_exc()[-2000:])
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[dryrun] ERROR {arch} {shape_name} ({mesh_name}): {e!r}",
              flush=True)
        return rec

    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    link_bytes = coll["total_link_bytes"]
    mf = model_flops(meta, shape)
    sharder_tmp = Sharder(mesh, get_config(arch))
    analytic = analytic_memory(get_config(arch), shape, meta, n_dev,
                               sharder_tmp.dp, sharder_tmp.tp)
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": link_bytes / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    rec.update(
        status="ok",
        meta=meta,
        devices=n_dev,
        lower_s=round(t1 - t0, 2),
        compile_s=round(t2 - t1, 2),
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_link_bytes_per_device=link_bytes,
        collectives={k: {kk: vv for kk, vv in v.items()}
                     for k, v in coll["per_kind"].items()},
        collective_count=coll["count"],
        top_collectives=coll.get("top_ops", []),
        memory=dict(
            argument_GiB=ma.argument_size_in_bytes / 2**30,
            output_GiB=ma.output_size_in_bytes / 2**30,
            temp_GiB=ma.temp_size_in_bytes / 2**30,
            alias_GiB=ma.alias_size_in_bytes / 2**30,
        ),
        analytic=analytic,
        roofline=dict(
            terms_s=terms,
            dominant=dominant,
            model_flops_global=mf,
            model_flops_per_device=mf / n_dev,
            hlo_flops_per_device=flops_dev,
            useful_ratio=(mf / n_dev) / flops_dev if flops_dev else None,
            roofline_fraction=(mf / n_dev / PEAK_FLOPS) / max(
                terms.values()) if max(terms.values()) > 0 else None,
        ),
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    dom_ms = terms[dominant] * 1e3
    print(f"[dryrun] OK {arch} {shape_name} ({mesh_name}) "
          f"compile={rec['compile_s']}s dominant={dominant}"
          f"({dom_ms:.2f}ms) frac={rec['roofline']['roofline_fraction']:.3f} "
          f"temp={rec['memory']['temp_GiB']:.2f}GiB "
          f"arg={rec['memory']['argument_GiB']:.2f}GiB", flush=True)
    return rec


def all_cells():
    cells = []
    for arch in all_arch_ids():
        for shape_name in SHAPES:
            cells.append((arch, shape_name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cells", help="comma-separated indices into all_cells()")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--sp", action="store_true", help="sequence-parallel acts")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (repeatable)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    if args.all or args.cells:
        cells = all_cells()
        if args.cells:
            idx = [int(i) for i in args.cells.split(",")]
            cells = [cells[i] for i in idx]
        for arch, shape_name in cells:
            mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
            path = os.path.join(args.out, mesh_name,
                                f"{arch}__{shape_name}{args.tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] EXISTS {arch} {shape_name}", flush=True)
                continue
            run_cell(arch, shape_name, args.multi_pod, args.out, sp=args.sp,
                     overrides=overrides, accum_override=args.accum,
                     tag=args.tag)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        run_cell(args.arch, args.shape, args.multi_pod, args.out, sp=args.sp,
                 overrides=overrides, accum_override=args.accum, tag=args.tag)


if __name__ == "__main__":
    main()
