import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""BERT-large dry-run on the production mesh -- the paper's exact experiment.

Lowers the paper-faithful pure-DP train step (shard_map + explicit gradient
exchange) for BERT-large phase-1/phase-2 shapes under each collective
strategy and records the collective schedule + roofline terms:

  psum          -> XLA-native all-reduce        (NCCL auto topology)
  ring          -> lax.ppermute ring            (the paper's NCCL ring [31])
  hierarchical  -> reduce-scatter(ICI) + cross-pod psum + all-gather(ICI)
                   (the paper's PCIe-vs-network schedule, multi-pod mesh)
  bucketed      -> ~25 MB per-bucket all-reduces (the paper's Fig 2 overlap)

  PYTHONPATH=src python -m repro.launch.bert_dryrun [--phase 1|2]
"""
import argparse
import json
import time
from pathlib import Path

import jax

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.core.amp import make_policy
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import HW, make_production_mesh
from repro.models import api
from repro.train.phases import bert_phases
from repro.train.train_step import init_train_state, make_train_step_dp
from repro.utils import logger


def run(strategy: str, phase, multi_pod: bool, out_dir: Path) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config("bert-large")
    tcfg = TrainConfig(precision="bf16", accum_steps=4,
                       collective_strategy=strategy)
    step, b_struct = make_train_step_dp(cfg, tcfg, mesh, phase.shape)
    param_shapes, _ = api.abstract_params(cfg)
    state_struct = jax.eval_shape(
        lambda p: init_train_state(p, make_policy("bf16"), tcfg),
        param_shapes)
    t0 = time.time()
    compiled = step.lower(state_struct, b_struct).compile()
    t_compile = time.time() - t0
    cost = hlo_analyze(compiled.as_text())
    colls = {k: v for k, v in cost["collective_bytes"].items() if v}
    coll_s = sum((2.0 if k == "all-reduce" else 1.0) * v / HW["ici_bw"]
                 for k, v in colls.items())
    rec = dict(strategy=strategy, phase=phase.name,
               mesh="2x16x16" if multi_pod else "16x16",
               compile_s=round(t_compile, 1),
               flops_per_device=cost["flops"],
               compute_s=cost["flops"] / HW["peak_flops_bf16"],
               collective_s=coll_s,
               collectives={k: dict(bytes=v,
                                    count=cost["collective_counts"][k])
                            for k, v in colls.items()})
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"bert_{phase.name}_{strategy}"
     f"{'_multipod' if multi_pod else ''}.json").write_text(
        json.dumps(rec, indent=2))
    logger.info("bert %s %-13s [%s]: compile %.0fs  coll %.0fms  %s",
                phase.name, strategy, rec["mesh"], t_compile, coll_s * 1e3,
                {k: f"{v['bytes'] / 1e9:.1f}GB x{v['count']:.0f}"
                 for k, v in rec["collectives"].items()})
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", type=int, default=1)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)
    phase = bert_phases(900, 100)[args.phase - 1]
    out = Path(args.out)
    for strategy in ("psum", "bucketed", "ring"):
        run(strategy, phase, multi_pod=False, out_dir=out)
    # hierarchical needs the pod axis: the paper's slow-link schedule
    run("hierarchical", phase, multi_pod=True, out_dir=out)
    run("psum", phase, multi_pod=True, out_dir=out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
