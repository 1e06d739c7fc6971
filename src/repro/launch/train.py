"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
      --steps 50 --batch 8 --seq 256 [--smoke] [--precision bf16] \
      [--strategy psum|ring|hierarchical|bucketed] [--accum 4] \
      [--dp --grad-compression none|fp16|int8] \
      [--overlap --bucket-bytes N] \
      [--ckpt-dir DIR --ckpt-every 100 --resume] [--loss-log FILE]

``--overlap`` switches the gradient exchange to the overlapped drain
schedule (packed per-bucket collectives inside the last micro-batch's
backward; bit-identical losses -- see core/grad_accum.py).  It and the
bucket size are fingerprinted (ov=/bb=) alongside the wire format.  To
see where a step's time goes, run under ``jax.profiler.trace``: the loop
and the step carry profiler spans and named scopes (README.md,
"Profiling a training run").

``--smoke`` swaps in the reduced same-family config so any architecture can
be exercised on CPU.  On a one-device host the mesh is (1, n_devices);
``--dp`` selects the paper-faithful pure-data-parallel shard_map path with
the explicit collective strategy.

Fault tolerance: ``--resume`` restores the newest valid checkpoint in
``--ckpt-dir`` (including the data-stream cursor, so the resumed loss
trajectory is bit-identical to an uninterrupted run), and the
``REPRO_FAULTS`` env var injects deterministic crashes / torn checkpoint
writes / NaN steps via train/faults.py -- the CI chaos step drives this
CLI that way.  ``--loss-log`` appends one JSON line per logged step (use
``--log-every 1`` for the exact-resume comparison).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import lm_batches
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.sharding import make_rules
from repro.train.train_step import (dp_state_shardings, init_train_state,
                                    make_train_step_dp, make_train_step_gspmd,
                                    state_shardings)
from repro.train.trainer import train_loop
from repro.utils import logger, tree_count


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--optimizer", default="lamb")
    ap.add_argument("--strategy", default="psum")
    ap.add_argument("--dp", action="store_true",
                    help="paper-faithful pure-DP shard_map mode")
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "fp16", "int8"),
                    help="compress the gradient exchange (requires --dp); "
                    "error feedback rides in TrainState and checkpoints")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped drain exchange (requires --dp): packed "
                    "per-bucket collectives issued inside the last "
                    "micro-batch's backward region; losses stay "
                    "bit-identical to the serial schedule")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="gradient exchange bucket size in bytes "
                    "(default: TrainConfig.bucket_bytes)")
    ap.add_argument("--pure-dp", action="store_true",
                    help="ZeRO-1 pure data parallelism (GSPMD mode)")
    ap.add_argument("--moe-impl", default="a2a")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=500)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest valid checkpoint")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--loss-log", default=None,
                    help="append {'step','loss'} JSON lines here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if cfg.is_encoder_only:
        raise SystemExit("use examples/pretrain_bert.py for BERT")

    if args.grad_compression != "none" and not args.dp:
        raise SystemExit("--grad-compression requires --dp (the explicit-"
                         "collective shard_map mode owns the wire format)")
    if args.overlap and not args.dp:
        raise SystemExit("--overlap requires --dp (the explicit-collective "
                         "shard_map mode owns the exchange schedule)")
    tcfg_kw = {}
    if args.bucket_bytes is not None:
        tcfg_kw["bucket_bytes"] = args.bucket_bytes
    tcfg = TrainConfig(precision=args.precision, accum_steps=args.accum,
                       collective_strategy=args.strategy,
                       grad_compression=args.grad_compression,
                       overlap_exchange=args.overlap,
                       optimizer=args.optimizer, total_steps=args.steps,
                       warmup_steps=max(2, args.steps // 10),
                       moe_impl=args.moe_impl, pure_dp=args.pure_dp,
                       seed=args.seed, **tcfg_kw)
    shape = InputShape("cli", args.seq, args.batch, "train")
    mesh = make_host_mesh()
    rules = make_rules(fsdp=tcfg.fsdp, pure_dp=tcfg.pure_dp)
    policy = make_policy(tcfg.precision)

    params, specs = api.init_params(jax.random.PRNGKey(args.seed), cfg)
    logger.info("arch %s: %.2fM params (smoke=%s)", cfg.arch_id,
                tree_count(params) / 1e6, args.smoke)
    state = init_train_state(params, policy, tcfg,
                             world=mesh.devices.size)
    del params

    if args.dp:
        step_fn, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
        placement = dp_state_shardings(state, mesh)
    else:
        shapes, specs_t = api.abstract_params(cfg)
        step_fn, _ = make_train_step_gspmd(cfg, tcfg, mesh, rules, specs_t,
                                           shapes, shape)
        placement = state_shardings(specs_t, shapes, mesh, rules)
    # where the step leaves the state: the step then compiles once
    state = jax.device_put(state, placement)

    class BatchStream:
        """Decorates the LMStream with the extra modality fields while
        forwarding its resume cursor (state_dict/load_state_dict)."""

        def __init__(self):
            self.inner = lm_batches(args.seed, cfg.vocab_size, args.batch,
                                    args.seq)

        def state_dict(self):
            return self.inner.state_dict()

        def load_state_dict(self, s):
            self.inner.load_state_dict(s)

        def __iter__(self):
            return self

        def __next__(self):
            out = {"tokens": next(self.inner)["tokens"]}
            if cfg.is_encoder_decoder:
                out["frames"] = 0.1 * np.random.default_rng(0).standard_normal(
                    (args.batch, cfg.enc_seq, cfg.d_model)).astype(np.float32)
            if cfg.n_vision_tokens:
                out["vision"] = 0.1 * np.random.default_rng(0).standard_normal(
                    (args.batch, cfg.n_vision_tokens,
                     cfg.d_model)).astype(np.float32)
            return out

    fingerprint = (f"{cfg.arch_id}:p={args.precision}:b={args.batch}x"
                   f"{args.seq}:opt={args.optimizer}:accum={args.accum}:"
                   f"seed={args.seed}:comp={args.grad_compression}:"
                   f"ov={int(tcfg.overlap_exchange)}:bb={tcfg.bucket_bytes}")

    metrics_hook = None
    if args.loss_log:
        def metrics_hook(m):
            with open(args.loss_log, "a") as f:
                f.write(json.dumps({"step": m["step"], "loss": m["loss"]})
                        + "\n")

    state, history = train_loop(
        step_fn, state, BatchStream(), total_steps=args.steps,
        log_every=args.log_every,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, metrics_hook=metrics_hook,
        config_fingerprint=fingerprint, seed=args.seed,
        tokens_per_step=args.batch * args.seq)
    if history:
        logger.info("final loss: %.4f", history[-1]["loss"])
    else:
        logger.info("nothing to do: checkpoint already at %d steps",
                    args.steps)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
