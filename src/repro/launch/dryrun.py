import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh with ShapeDtypeStruct inputs (no allocation), and extract the roofline
terms (deliverables (e) and (g)).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Roofline terms (per device, TPU v5e constants in launch/mesh.py):
  compute    = HLO_FLOPs / peak_FLOP/s
  memory     = HLO_bytes / HBM_bw
  collective = sum over collective ops of (algorithmic bytes / link_bw)
with per-device FLOPs/bytes from ``compiled.cost_analysis()`` and collective
op shapes parsed from the post-SPMD optimized HLO (``compiled.as_text()``).
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, get_config, get_shape, INPUT_SHAPES
from repro.configs.base import TrainConfig
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import HW, make_production_mesh
from repro.models import api
from repro.sharding import make_rules
from repro.utils import human_bytes, logger


def collective_seconds(coll_bytes: dict, *, ici_bw: float) -> float:
    """Algorithmic time model: all-reduce moves 2x its bytes per device
    (reduce-scatter + all-gather rings); others move ~1x.  Bytes are already
    per-device (post-SPMD shapes) and loop-corrected."""
    t = 0.0
    for kind, b in coll_bytes.items():
        factor = 2.0 if kind == "all-reduce" else 1.0
        t += factor * b / ici_bw
    return t


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) useful-compute estimate."""
    n_active = cfg.param_count(active_only=True)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token


def build_step(cfg, shape, mesh, rules, tcfg):
    """Returns (jitted_fn, example_struct_args) for the shape's step kind."""
    param_shapes, param_specs = api.abstract_params(cfg)

    if shape.kind == "train":
        from repro.train.train_step import (TrainState, make_train_step_gspmd,
                                            state_shardings)
        from repro.core.amp import make_policy
        from repro.train.train_step import init_train_state
        step, b_struct = make_train_step_gspmd(
            cfg, tcfg, mesh, rules, param_specs, param_shapes, shape)
        state_struct = jax.eval_shape(
            lambda p: init_train_state(p, make_policy(tcfg.precision), tcfg),
            param_shapes)
        return step, (state_struct, b_struct)
    # serving: weights are stored in the compute dtype (bf16 checkpoints)
    from repro.core.amp import make_policy
    pdtype = make_policy(tcfg.precision).param_dtype
    serve_params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, pdtype)
        if jnp.issubdtype(s.dtype, jnp.floating) else s, param_shapes)
    if shape.kind == "prefill":
        from repro.serve.serve_step import make_prefill_step
        step, b_struct, _ = make_prefill_step(
            cfg, tcfg, mesh, rules, param_specs, serve_params, shape)
        return step, (serve_params, b_struct)
    # decode
    from repro.serve.serve_step import make_decode_step
    step, st_struct = make_decode_step(
        cfg, tcfg, mesh, rules, param_specs, serve_params, shape)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return step, (serve_params, tok, st_struct)


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            tcfg: TrainConfig, out_dir: Path, verbose: bool = True,
            seq_shard: bool = False, vmem_flash: bool = False,
            tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = api.shape_supported(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "multi_pod": multi_pod}
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{arch}_{shape_name}_{mesh_name}.json").write_text(
            json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(fsdp=tcfg.fsdp, multi_pod=multi_pod,
                       seq_shard=seq_shard, pure_dp=tcfg.pure_dp)
    chips = mesh.size

    t0 = time.time()
    step, args = build_step(cfg, shape, mesh, rules, tcfg)
    lowered = step.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    t0 = time.time()
    scopes = ("flash_attention", "wkv6_kernel", "mamba_ssm_kernel") \
        if vmem_flash else ()
    cost = hlo_analyze(hlo, vmem_scopes=scopes)  # loop-corrected, per-device
    t_analyze = time.time() - t0

    flops_total = float(cost["flops"])
    bytes_total = float(cost["bytes"])
    compute_s = flops_total / HW["peak_flops_bf16"]
    memory_s = bytes_total / HW["hbm_bw"]
    coll_s = collective_seconds(cost["collective_bytes"],
                                ici_bw=HW["ici_bw"])
    mflops = model_flops(cfg, shape)
    mflops_dev = mflops / chips

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    peak = getattr(mem, "peak_memory_in_bytes", 0)
    arg_b = getattr(mem, "argument_size_in_bytes", 0)

    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        analyze_s=round(t_analyze, 2),
        memory=dict(  # per-device (post-SPMD executable)
            argument_bytes=arg_b,
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            peak_bytes=peak,
            fits_16g_hbm=bool(arg_b + getattr(mem, "temp_size_in_bytes", 0)
                              < 16e9),
        ),
        hlo_flops_per_device=flops_total,
        hlo_bytes_per_device=bytes_total,
        xla_cost_analysis=dict(  # raw, loop-UNcorrected, for reference
            flops=float(xla_cost.get("flops", 0.0)),
            bytes_accessed=float(xla_cost.get("bytes accessed", 0.0)),
        ),
        collectives={k: {"bytes": cost["collective_bytes"][k],
                         "count": cost["collective_counts"][k]}
                     for k in cost["collective_bytes"]},
        roofline=dict(
            compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
            dominant=dominant,
            model_flops_total=mflops,
            model_flops_per_device=mflops_dev,
            useful_compute_ratio=(mflops_dev / flops_total
                                  if flops_total else None),
        ),
        params_total=cfg.param_count(),
        params_active=cfg.param_count(active_only=True),
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    fn = out_dir / f"{arch}_{shape_name}_{mesh_name}{tag}.json"
    fn.write_text(json.dumps(rec, indent=2, default=str))
    if verbose:
        tmp_b = rec["memory"]["temp_bytes"] or 0
        logger.info(
            "%s x %s [%s]: compile %.1fs | args/dev %s temp/dev %s | "
            "flops/dev %.3e bytes/dev %.3e | roofline c=%.1fms m=%.1fms "
            "coll=%.1fms dom=%s useful=%.2f",
            arch, shape_name, mesh_name, t_compile,
            human_bytes(arg_b), human_bytes(tmp_b),
            flops_total, bytes_total, compute_s * 1e3, memory_s * 1e3,
            coll_s * 1e3, dominant,
            (rec["roofline"]["useful_compute_ratio"] or 0))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--moe-impl", default="a2a")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--shard-grads", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--vmem-flash", action="store_true",
                    help="model flash-attention intermediates as VMEM-"
                         "resident (the Pallas kernel on the TPU target)")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json (perf iterations)")
    args = ap.parse_args(argv)

    tcfg = TrainConfig(precision=args.precision, accum_steps=args.accum,
                       moe_impl=args.moe_impl, fsdp=not args.no_fsdp,
                       remat=not args.no_remat,
                       shard_grads=args.shard_grads,
                       pure_dp=args.pure_dp)
    out_dir = Path(args.out)
    pairs = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(
                        arch, shape, multi_pod=mp, tcfg=tcfg,
                        out_dir=out_dir, seq_shard=args.seq_shard,
                        vmem_flash=args.vmem_flash, tag=args.tag))
                except Exception as e:  # noqa: BLE001 -- report & continue
                    failures += 1
                    logger.error("FAILED %s x %s (multi_pod=%s): %s",
                                 arch, shape, mp, e)
                    results.append({"arch": arch, "shape": shape,
                                    "multi_pod": mp, "status": "failed",
                                    "error": str(e)[:500]})
    n_ok = sum(r.get("status") == "ok" for r in results)
    n_skip = sum(r.get("status") == "skipped" for r in results)
    logger.info("dry-run done: %d ok, %d skipped, %d failed",
                n_ok, n_skip, failures)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
