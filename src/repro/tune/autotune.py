"""Measured comm autotuner: successive halving over the exchange config.

The paper (and ROADMAP's model-advisor note, after e2eAIOK) argues train
configs on commodity clusters should be *measured*, not guessed: the best
(bucket_bytes, accum_steps, strategy, compression, overlap) point depends
on the interconnect, the model's leaf-size mix, and the per-op dispatch
cost of the runtime -- none of which an analytic model sees.  This module
searches that space with short REAL ``dp_shardmap`` train steps:

  * ``make_grid``            -- cartesian candidate grid with validity
                                filtering (hierarchical needs an even pod
                                split; compression/overlap are DP-only so
                                every candidate is, by construction);
  * ``successive_halving``   -- classic budget-doubling race: every round
                                times all surviving candidates at the
                                current ``iters`` budget, keeps the top
                                ``keep_frac`` by tokens/s, doubles the
                                budget, until one survivor (or
                                ``max_rounds``) remains.  The measure
                                function is injected, so the search logic
                                is unit-testable without devices;
  * ``run_autotune``         -- wires a real measurer (model + mesh +
                                ``make_train_step_dp``) into the search and
                                returns ``(best, trials)``; the CLI in
                                ``__main__`` re-execs itself with forced
                                host devices (XLA fixes the device count at
                                first import) and merge-writes a
                                ``train_autotune`` section -- winning config
                                + full trial table -- into BENCH_train.json.

Objective: tokens/s at fixed global batch (= step time; accum_steps rides
in the grid because it changes the comm:compute ratio and the overlap
drain window, not the samples per optimizer step).
"""
from __future__ import annotations

import itertools
import json
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

DEFAULT_SPACE = {
    "bucket_bytes": [1 << 16, 1 << 20],
    "accum_steps": [1, 4],
    "strategy": ["psum", "ring", "hierarchical", "bucketed"],
    "compression": ["none", "fp16", "int8"],
    "overlap": [False, True],
}


def make_grid(space: Optional[Dict[str, Sequence]] = None, *,
              devices: int = 4, global_batch: int = 32) -> List[dict]:
    """Cartesian product of ``space`` with invalid candidates filtered out.

    Filters: hierarchical needs >= 4 devices and an even (2, n/2) pod
    split; accum_steps must divide the per-device batch; redundant
    bucket_bytes points are deduped for cells whose exchange ignores the
    bucket size (uncompressed, non-bucketed, serial schedule -- psum/ring/
    hierarchical wire the whole tree regardless, so racing three identical
    configs would waste budget).
    """
    space = dict(DEFAULT_SPACE, **(space or {}))
    per_dev = global_batch // max(devices, 1)
    grid, seen = [], set()
    for bb, acc, strat, comp, ov in itertools.product(
            space["bucket_bytes"], space["accum_steps"], space["strategy"],
            space["compression"], space["overlap"]):
        if strat == "hierarchical" and (devices < 4 or devices % 2):
            continue
        if per_dev % acc:
            continue
        bucketed = ov or comp == "int8" or strat == "bucketed"
        key = (bb if bucketed else 0, acc, strat, comp, ov)
        if key in seen:
            continue
        seen.add(key)
        grid.append({"bucket_bytes": bb, "accum_steps": acc,
                     "strategy": strat, "compression": comp, "overlap": ov})
    return grid


def tokens_per_s(step_s: float, *, global_batch: int, seq: int) -> float:
    return global_batch * seq / max(step_s, 1e-12)


def successive_halving(candidates: List[dict],
                       measure: Callable[[dict, int], float], *,
                       iters0: int = 2, keep_frac: float = 0.5,
                       max_rounds: int = 3,
                       growth: int = 2) -> Tuple[dict, List[dict]]:
    """Race ``candidates``; returns (best_trial, full_trial_table).

    ``measure(candidate, iters) -> tokens_per_s`` (higher is better; it may
    raise -- a failed candidate is recorded with ``error`` and eliminated).
    Every trial row carries round / iters / tokens_per_s, so the written
    table shows the whole race, not just the winner.
    """
    alive = list(candidates)
    trials: List[dict] = []
    iters = iters0
    best_row: Optional[dict] = None
    for rnd in range(max_rounds):
        scored = []
        for cand in alive:
            row = dict(cand, round=rnd, iters=iters)
            try:
                row["tokens_per_s"] = float(measure(cand, iters))
                scored.append(row)
            except Exception as e:  # noqa: BLE001 -- candidate, not harness
                row["error"] = f"{type(e).__name__}: {e}"
            trials.append(row)
        if not scored:
            raise RuntimeError("autotune: every candidate failed")
        scored.sort(key=lambda r: r["tokens_per_s"], reverse=True)
        best_row = scored[0]
        if len(scored) == 1 or rnd == max_rounds - 1:
            break
        keep = max(1, math.ceil(len(scored) * keep_frac))
        alive = [{k: r[k] for k in ("bucket_bytes", "accum_steps",
                                    "strategy", "compression", "overlap")}
                 for r in scored[:keep]]
        iters *= growth
    return best_row, trials


# ---------------------------------------------------------------------------
# Real measurement: short dp_shardmap steps per candidate.
# ---------------------------------------------------------------------------

def _make_measure(arch: str, d_model: int, seq: int, global_batch: int,
                  warmup: int = 1) -> Callable[[dict, int], float]:
    import time

    import jax
    import numpy as np

    from repro.configs import get_config, smoke_variant
    from repro.configs.base import InputShape, TrainConfig
    from repro.core.amp import make_policy
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.train.train_step import init_train_state, make_train_step_dp

    n = len(jax.devices())
    cfg = smoke_variant(get_config(arch), d_model=d_model)
    shape = InputShape("tune", seq, global_batch, "train")
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = api.make_synth_batch(jax.random.PRNGKey(1), cfg, shape)
    pol = make_policy("f32")

    def measure(cand: dict, iters: int) -> float:
        if cand["strategy"] == "hierarchical" and n >= 4 and n % 2 == 0:
            mesh = make_mesh((2, n // 2), ("pod", "data"))
        else:
            mesh = make_mesh((n,), ("data",))
        tcfg = TrainConfig(precision="f32", accum_steps=cand["accum_steps"],
                           collective_strategy=cand["strategy"],
                           grad_compression=cand["compression"],
                           overlap_exchange=cand["overlap"],
                           bucket_bytes=cand["bucket_bytes"],
                           total_steps=100, warmup_steps=2)
        step_fn, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
        state = init_train_state(params, pol, tcfg, world=n)
        for _ in range(warmup):
            state, m = step_fn(state, batch)
            jax.block_until_ready(m["loss"])
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            state, m = step_fn(state, batch)
            jax.block_until_ready(m["loss"])
            ts.append(time.perf_counter() - t0)
        return tokens_per_s(float(np.median(ts)), global_batch=global_batch,
                            seq=seq)

    return measure


def run_autotune(*, arch: str = "bert-large", d_model: int = 64,
                 seq: int = 32, global_batch: int = 32,
                 space: Optional[Dict[str, Sequence]] = None,
                 iters0: int = 2, max_rounds: int = 3,
                 keep_frac: float = 0.5) -> Tuple[dict, List[dict]]:
    """Measured search over the live device set; call inside one process.

    Returns (best_trial, trials).  ``best_trial`` also carries the baseline
    comparison: ``speedup_vs_default`` against the repo's default exchange
    config (serial psum, uncompressed, accum 1) measured with the same
    budget as the final round.
    """
    import jax

    measure = _make_measure(arch, d_model, seq, global_batch)
    grid = make_grid(space, devices=len(jax.devices()),
                     global_batch=global_batch)
    best, trials = successive_halving(grid, measure, iters0=iters0,
                                      keep_frac=keep_frac,
                                      max_rounds=max_rounds)
    default = {"bucket_bytes": 25 * 2 ** 20, "accum_steps": 1,
               "strategy": "psum", "compression": "none", "overlap": False}
    default_tps = float(measure(default, best["iters"]))
    best = dict(best, speedup_vs_default=round(
        best["tokens_per_s"] / max(default_tps, 1e-12), 3),
        default_tokens_per_s=round(default_tps, 1))
    return best, trials


# ---------------------------------------------------------------------------
# CLI: forced-device subprocess -> train_autotune section of BENCH_train.
# ---------------------------------------------------------------------------

def _cli(argv=None) -> int:
    import argparse
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[3]

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--arch", default="bert-large")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--iters0", type=int, default=2)
    ap.add_argument("--max-rounds", type=int, default=3)
    ap.add_argument("--space-json", default=None,
                    help="JSON dict overriding DEFAULT_SPACE dims "
                    "(e.g. the CI tiny grid)")
    ap.add_argument("--out", default="BENCH_train.json")
    args = ap.parse_args(argv)
    space = json.loads(args.space_json) if args.space_json else None

    if args.worker:
        best, trials = run_autotune(
            arch=args.arch, d_model=args.d_model, seq=args.seq,
            global_batch=args.global_batch, space=space,
            iters0=args.iters0, max_rounds=args.max_rounds)
        print("RESULT_JSON:" + json.dumps({"best": best, "trials": trials}))
        return 0

    env = dict(os.environ)
    # the worker searches on forced host devices; pinning its platform
    # keeps it off an accelerator the parent process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={args.devices}"
    env["PYTHONPATH"] = str(repo / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "repro.tune.autotune", "--worker",
           "--devices", str(args.devices), "--arch", args.arch,
           "--d-model", str(args.d_model), "--seq", str(args.seq),
           "--global-batch", str(args.global_batch),
           "--iters0", str(args.iters0),
           "--max-rounds", str(args.max_rounds)]
    if args.space_json:
        cmd += ["--space-json", args.space_json]
    proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"autotune worker failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT_JSON:"):
            payload = json.loads(line[len("RESULT_JSON:"):])
    if payload is None:
        raise RuntimeError(f"autotune worker produced no RESULT_JSON:\n"
                           f"{proc.stdout}\n{proc.stderr}")

    best, trials = payload["best"], payload["trials"]
    measured = [t for t in trials if "tokens_per_s" in t]
    section = {
        "bench": "train_autotune",
        "config": {"arch": args.arch, "d_model": args.d_model,
                   "seq": args.seq, "global_batch": args.global_batch,
                   "devices": args.devices, "iters0": args.iters0,
                   "max_rounds": args.max_rounds,
                   "space": space or {k: list(v) for k, v in
                                      DEFAULT_SPACE.items()}},
        "best": best,
        "trials": trials,
        "derived": {
            "best_tokens_per_s": round(best["tokens_per_s"], 1),
            "speedup_vs_default": best["speedup_vs_default"],
            "n_trials": len(trials),
            "n_failed": len(trials) - len(measured),
        },
    }
    sys.path.insert(0, str(repo))
    from benchmarks.serve_paged import write_section
    write_section(args.out, "train_autotune", section)
    for t in sorted(measured, key=lambda r: -r["tokens_per_s"])[:8]:
        print(f"round {t['round']} iters {t['iters']:2d} "
              f"{t['strategy']:>12s}/{t['compression']:<4s} "
              f"ov={int(t['overlap'])} acc={t['accum_steps']} "
              f"bb={t['bucket_bytes']:>8d}  {t['tokens_per_s']:8.0f} tok/s")
    print(f"best: {json.dumps(best)}")
    print(f"wrote {args.out} [train_autotune]")
    return 0


if __name__ == "__main__":
    raise SystemExit(_cli())
