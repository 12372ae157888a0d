import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell with ShapeDtypeStruct stand-ins (no allocation), print
memory_analysis/cost_analysis, and emit roofline rows to JSON artifacts.

MUST set XLA_FLAGS before any jax import (above) — jax locks the device
count at first init. Run as:

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out artifacts/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import configs, optim
from repro.configs.base import SHAPES_BY_NAME, ShapeSpec, cell_is_runnable
from repro.distributed.sharding import ShardingRules, batch_shardings
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.launch.serve import make_prefill, make_serve_step
from repro.launch.train import make_train_step
from repro.models.model import Model
from repro.models.transformer import ParallelCtx
from repro.roofline.analysis import HW, analyze_compiled

# zamba2's shared blocks attend over a 4096 sliding window at 500k: the
# model's published context
LONG_WINDOW = {"zamba2-7b": 4096}


def build_model(arch: str, shape: ShapeSpec, mesh,
                overrides: Optional[dict] = None,
                opt: Optional[dict] = None) -> Model:
    """opt: perf-iteration flags (§Perf) — pad_heads: TP head padding."""
    opt = opt or {}
    cfg = configs.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if opt.get("pad_heads"):
        cfg = cfg.tp_pad_heads(mesh.shape["model"])
    window = None
    if shape.name == "long_500k":
        window = LONG_WINDOW.get(arch)
    pctx = ParallelCtx(mesh=mesh, ep=(cfg.family == "moe"))
    return Model(cfg, pctx=pctx, window=window)


def lower_cell(arch: str, shape_name: str, mesh, *,
               fsdp: bool = True, donate: bool = True,
               overrides: Optional[dict] = None,
               opt: Optional[dict] = None):
    """Returns (lowered, n_tokens, kind, model)."""
    shape = SHAPES_BY_NAME[shape_name]
    model = build_model(arch, shape, mesh, overrides, opt)
    cfg = model.cfg
    rules = ShardingRules(mesh, fsdp=fsdp)

    key = jax.random.PRNGKey(0)
    p_spec = jax.eval_shape(model.init, key)
    p_shard = rules.shardings(p_spec)
    batch_spec = model.input_specs(shape)

    if shape.kind == "train":
        opt = optim.adamw()
        o_spec = jax.eval_shape(opt.init, p_spec)
        o_shard = jax.tree_util.tree_map(
            lambda leaf_spec: None, o_spec)
        # opt moments share the param sharding; count replicated
        o_shard = {
            "mu": rules.shardings(o_spec["mu"]),
            "nu": rules.shardings(o_spec["nu"]),
            "count": jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()),
        }
        b_shard = batch_shardings(mesh, batch_spec, shape.global_batch)
        lr_shard = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        step = make_train_step(model, opt)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard,
                                             lr_shard),
                         donate_argnums=(0, 1) if donate else ())
        lowered = jitted.lower(p_spec, o_spec, batch_spec,
                               jax.ShapeDtypeStruct((), jnp.float32))
        n_tokens = shape.tokens
        kind = "train"
    elif shape.kind == "prefill":
        b_shard = batch_shardings(mesh, batch_spec, shape.global_batch)
        fn = make_prefill(model, max_len=shape.seq_len)
        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(p_spec, batch_spec)
        n_tokens = shape.tokens
        kind = "inference"
    else:  # decode
        cache_spec = batch_spec.pop("_cache")
        b_shard = batch_shardings(mesh, batch_spec, shape.global_batch)
        c_shard = batch_shardings(mesh, cache_spec, shape.global_batch)
        fn = make_serve_step(model)
        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard, c_shard),
                         donate_argnums=(2,) if donate else ())
        lowered = jitted.lower(p_spec, batch_spec, cache_spec)
        n_tokens = shape.global_batch  # one new token per sequence
        kind = "inference"
    return lowered, n_tokens, kind, model


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             fsdp: bool = True, overrides: Optional[dict] = None,
             opt: Optional[dict] = None,
             tag: str = "") -> Optional[dict]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = mesh_name(mesh)
    label = f"{arch} × {shape_name} × {mname}" + (f" [{tag}]" if tag else "")
    if not cell_is_runnable(arch, shape_name):
        print(f"[dryrun] SKIP {label} (documented: this cell needs "
              f"sub-quadratic attention or a decoder arch)")
        return None
    t0 = time.perf_counter()
    try:
        with mesh:
            lowered, n_tokens, kind, model = lower_cell(
                arch, shape_name, mesh, fsdp=fsdp, overrides=overrides,
                opt=opt)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        # MODEL_FLOPS uses the ASSIGNED (unpadded) architecture
        base_cfg = configs.get(arch)
        if overrides:
            base_cfg = dataclasses.replace(base_cfg, **overrides)
        n_params = base_cfg.active_param_count()   # 6·N_active·D for MoE
        rep = analyze_compiled(
            compiled, arch=arch, shape=shape_name, mesh_name=mname,
            chips=mesh.size, n_params=n_params, n_tokens=n_tokens, kind=kind,
            hw=HW.for_arch("v5e"))
        from repro.roofline.analysis import attn_kernel_io_bytes
        rep.kernel_io_bytes = attn_kernel_io_bytes(
            model.cfg, SHAPES_BY_NAME[shape_name].tokens
            if kind != "inference" or shape_name.startswith("prefill")
            else n_tokens, mesh, kind)
        row = rep.row()
        row.update({
            "bytes_by_tag_gb": {k: v / 1e9 for k, v in rep.bytes_by_tag.items()},
            "kernel_io_gb_dev": rep.kernel_io_bytes / 1e9,
            "t_memory_kernel_s": rep.t_memory_kernel,
            "roofline_fraction_kernel": rep.roofline_fraction_kernel,
        })
        row.update({
            "compile_s": time.perf_counter() - t0,
            "arg_gb_dev": ma.argument_size_in_bytes / 1e9,
            "temp_gb_dev": ma.temp_size_in_bytes / 1e9,
            "alias_gb_dev": ma.alias_size_in_bytes / 1e9,
            "coll_by_kind_gb": {k: v / 1e9 for k, v in rep.coll_by_kind.items()},
            "coll_traffic_gb_dev": rep.coll_traffic_bytes / 1e9,
            "tag": tag or "baseline",
        })
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{mname}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(row, f, indent=1)
        print(f"[dryrun] OK   {label}: "
              f"mem/dev arg={row['arg_gb_dev']:.2f}+tmp={row['temp_gb_dev']:.2f}GB "
              f"flops/dev={row['hlo_gflops_dev']:.1f}G "
              f"coll/dev={row['coll_gb_dev']:.3f}GB "
              f"bottleneck={row['bottleneck']} "
              f"roofline={row['roofline_fraction']:.3f} "
              f"({row['compile_s']:.0f}s)")
        return row
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug; report it
        print(f"[dryrun] FAIL {label}: {type(e).__name__}: {e}")
        traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": mname,
                "error": f"{type(e).__name__}: {e}", "tag": tag or "baseline"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    args = ap.parse_args()

    archs = list(configs.available()) if args.arch == "all" else [args.arch]
    shapes = (list(SHAPES_BY_NAME) if args.shape == "all"
              else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                row = run_cell(arch, shape, mp, args.out,
                               fsdp=not args.no_fsdp)
                jax.clear_caches()   # bound host RAM across 64 compiles
                if row is None:
                    n_skip += 1
                elif "error" in row:
                    n_fail += 1
                else:
                    n_ok += 1
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
