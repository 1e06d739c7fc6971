"""End-to-end driver: two-phase BERT pretraining (the paper's experiment).

  PYTHONPATH=src python examples/pretrain_bert.py \
      [--phase1-steps 108] [--phase2-steps 12] [--full] [--d-model 256] \
      [--precision bf16] [--accum 4] \
      [--strategy psum|ring|hierarchical|bucketed] [--dp]

Reproduces the paper's §3.3/§5.2 flow:
  phase 1 (seq 128, 20 predictions) then
  phase 2 (seq 512, 80 predictions),
with the paper's optimization stack: data sharding, AMP, gradient
accumulation, LAMB, and the selected gradient-collective strategy.  Phase 2
starts from phase 1's final state (the paper's phase-2 init).

By default the model is a reduced same-family BERT that trains on a CPU
(~10M params; ``--d-model 768 --full-depth`` for ~100M).  ``--full`` trains
the published BERT-large config (24L, d 1024, 16 heads, vocab 30522), which
needs an accelerator; ``chip_smoke.py`` drives ``train_phases`` that way.
"""
import argparse
import dataclasses
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.configs import get_config, smoke_variant
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import ShardedLoader, prepare_bert_data
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.sharding import make_rules
from repro.train.phases import Phase, bert_phases
from repro.train.train_step import (dp_state_shardings, init_train_state,
                                    make_train_step_dp, make_train_step_gspmd,
                                    state_shardings)
from repro.train.trainer import train_loop
from repro.utils import logger, tree_count, use_compile_cache


def train_phases(cfg: ModelConfig, phases: List[Phase], mesh, *,
                 workdir: str, dp: bool = False, precision: str = "bf16",
                 accum: int = 4, strategy: str = "psum",
                 checkpoint: bool = True, resume: bool = False,
                 wrap_step: Optional[Callable] = None
                 ) -> Tuple[object, Dict[str, list]]:
    """Train ``phases`` in order, each through ShardedLoader -> train_loop.

    ``dp`` selects the paper's pure-DP shard_map step (explicit gradient
    exchange over ``mesh``'s axes); otherwise the GSPMD step.  Each phase's
    step is built for that phase's shape, and the state is placed where the
    step leaves it, so each phase compiles its step once.  ``wrap_step(phase,
    step)`` may return a wrapper around a phase's jitted step (timing,
    compile counting).  Returns (final state, {phase name: train_loop
    history}).
    """
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    logger.info("%s: %.1fM params", cfg.arch_id, tree_count(params) / 1e6)
    state, histories = None, {}
    for phase in phases:
        logger.info("=== %s: seq %d, %d preds, batch %d, %d steps ===",
                    phase.name, phase.seq_len, phase.n_predictions,
                    phase.global_batch, phase.steps)
        if phase.steps <= 0:
            continue
        # paper §4.1: shard the phase's data before training
        shard_dir = f"{workdir}/{phase.name}"
        prepare_bert_data(shard_dir, seq_len=phase.seq_len,
                          n_predictions=phase.n_predictions,
                          n_docs=120, vocab_size=cfg.vocab_size, n_shards=4)
        loader = ShardedLoader(shard_dir, worker=0, n_workers=1,
                               batch=phase.global_batch)
        tcfg = TrainConfig(precision=precision, accum_steps=accum,
                           collective_strategy=strategy, optimizer="lamb",
                           learning_rate=phase.learning_rate,
                           total_steps=phase.steps,
                           warmup_steps=max(2, phase.steps // 10))
        if state is None:
            state = init_train_state(params, make_policy(precision), tcfg,
                                     world=mesh.devices.size)
            del params
        if dp:
            step, _ = make_train_step_dp(cfg, tcfg, mesh, phase.shape)
            placement = dp_state_shardings(state, mesh)
        else:
            rules = make_rules()
            shapes, specs = api.abstract_params(cfg)
            step, _ = make_train_step_gspmd(cfg, tcfg, mesh, rules, specs,
                                            shapes, phase.shape)
            placement = state_shardings(specs, shapes, mesh, rules)
        state = jax.device_put(state, placement)
        if wrap_step is not None:
            step = wrap_step(phase, step)
        # per-phase checkpoint dirs: step numbering restarts each phase, so
        # a shared dir would alias phase-1 and phase-2 checkpoints
        state, histories[phase.name] = train_loop(
            step, state, loader, total_steps=phase.steps,
            log_every=max(1, phase.steps // 10),
            ckpt_dir=f"{workdir}/ckpt/{phase.name}" if checkpoint else None,
            ckpt_every=max(10, phase.steps // 2),
            resume=resume,
            config_fingerprint=f"bert:{phase.name}:{precision}",
            tokens_per_step=phase.global_batch * phase.seq_len)
        if histories[phase.name]:
            logger.info("%s final loss: %.4f", phase.name,
                        histories[phase.name][-1]["loss"])
    return state, histories


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase1-steps", type=int, default=108)
    ap.add_argument("--phase2-steps", type=int, default=12)
    ap.add_argument("--full", action="store_true",
                    help="published BERT-large widths (needs an accelerator)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--full-depth", action="store_true",
                    help="24 layers (BERT-large depth) instead of 2")
    ap.add_argument("--batch", type=int, default=16,
                    help="phase-1 global batch; phase 2 takes half")
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--strategy", default="psum")
    ap.add_argument("--dp", action="store_true",
                    help="paper-faithful pure-DP shard_map mode")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="resume each phase from its newest valid "
                         "checkpoint (needs a stable --workdir)")
    args = ap.parse_args()
    use_compile_cache()

    if args.full:
        cfg = get_config("bert-large")
        lr = 1e-4
    else:
        cfg = smoke_variant(get_config("bert-large"), d_model=args.d_model,
                            n_blocks=24 if args.full_depth else 2)
        cfg = dataclasses.replace(cfg, max_position=512)
        lr = 2e-3  # the reduced model trains at 20x the paper's rate
    n = len(jax.devices())
    mesh = make_host_mesh((n, 1) if args.dp else (1, n), ("data", "model"))
    workdir = args.workdir or tempfile.mkdtemp(prefix="repro_bert_")
    phases = bert_phases(args.phase1_steps, args.phase2_steps,
                         global_batch_p1=args.batch,
                         global_batch_p2=max(8, args.batch // 2),
                         learning_rate=lr)
    train_phases(cfg, phases, mesh, workdir=workdir, dp=args.dp,
                 precision=args.precision, accum=args.accum,
                 strategy=args.strategy, resume=args.resume)
    logger.info("two-phase pretraining complete; checkpoints in %s/ckpt",
                workdir)


if __name__ == "__main__":
    main()
