"""Smoke run of the training path on a TPU: BERT-large at published widths.

  python chip_smoke.py             # one chip: both pre-training phases
  python chip_smoke.py --chips 4   # four chips: phase-1 DP vs one device

One chip: ``examples/pretrain_bert.py``'s ``train_phases`` trains the
published BERT-large config (24L, d 1024, 16 heads, vocab 30522, random
weights from a seed) through ShardedLoader -> train_loop with LAMB, bf16 AMP,
gradient accumulation 2 and the pure-DP psum step: phase 1 at 32 x 128
(20 predictions), then phase 2 at 8 x 512 (80 predictions), 6 steps each.
Checks: one train-step compile per phase, finite losses, and a step-1 loss
within ``REF_LOSS_TOL`` of a float32 forward of the same parameters and
batch at the highest matmul precision.

Four chips: phase 1 only, 3 steps, per-chip batch 32 x 128 over a (4, 1)
data mesh with psum, against the same global batch (128) on one device of
this process at accumulation 8 -- the same micro-batches, so the per-step
losses and gradient norms agree up to summation order.  The compiled DP
step must contain an all-reduce.

Step times printed here are smoke numbers (a few steps, no warm-up window
discipline), not benchmark results.  The script runs everything in this
one process, fails unless JAX's first device is a TPU, and exits non-zero
on any failed check; only a passing run prints the final JSON line.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO / "src"), str(REPO / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from pretrain_bert import train_phases  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.amp import make_policy  # noqa: E402
from repro.core.grad_accum import split_microbatches  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.train.phases import bert_phases  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

PHASE1_BATCH, PHASE2_BATCH = 32, 8     # per chip: 4096 tokens per step each
ACCUM = 2
STEPS = 6                              # per phase; step 1 compiles
FOUR_CHIP_STEPS = 3
# |bf16 step-1 loss - fp32 reference| on a ~11-nat loss at init.  A CPU run
# of the same check at d 512, 4 layers, vocab 30522 differs by 2.6e-4.
REF_LOSS_TOL = 1e-2
# 4-chip DP vs one device, same micro-batches: only the gradient summation
# order differs, so the losses and gradient norms match to float noise
DP_LOSS_TOL = 1e-3
DP_GNORM_RTOL = 1e-3

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_NAME = "jit(train_step)"          # the jitted step both builders return


class StepProbe:
    """Wraps each phase's jitted step for ``train_phases(wrap_step=...)``.

    Before a phase's first step it compiles the step ahead of time (the
    compile time; jit reuses that executable) and keeps the compiled HLO's
    Pallas-kernel count and whether it all-reduces.  Every step is timed to
    ``block_until_ready`` and its loss and gradient norm kept.  A compile
    listener counts every compile of the train step, per phase: a state
    placed unlike the step's outputs shows up as a second compile.
    """

    def __init__(self):
        self.phases: dict = {}
        self.first_params = self.first_batch = None
        self._current = None

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._on_compile)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._on_compile)

    def _on_compile(self, event, duration, **kw):
        if event == COMPILE_EVENT and kw.get("fun_name") == STEP_NAME \
                and self._current is not None:
            self.phases[self._current]["compiles"] += 1

    def wrap(self, phase, step):
        rec = self.phases[phase.name] = dict(
            seq=phase.seq_len, batch=phase.global_batch, compiles=0,
            step_s=[], losses=[], grad_norms=[])
        self._current = phase.name

        def probed(state, batch):
            if not rec["step_s"]:
                if self.first_params is None:
                    self.first_params = jax.device_get(state.opt.master)
                    self.first_batch = batch
                t0 = time.perf_counter()
                hlo = step.lower(state, batch).compile().as_text()
                rec["compile_s"] = time.perf_counter() - t0
                rec["pallas_kernels"] = hlo.count(
                    'custom_call_target="tpu_custom_call"')
                rec["all_reduce"] = "all-reduce" in hlo
            t0 = time.perf_counter()
            state, metrics = jax.block_until_ready(step(state, batch))
            rec["step_s"].append(time.perf_counter() - t0)
            rec["losses"].append(float(metrics["loss"]))
            rec["grad_norms"].append(float(metrics["grad_norm"]))
            return state, metrics

        return probed


def fp32_reference_loss(cfg, params, batch, accum: int) -> float:
    """Loss of ``params`` on ``batch`` in float32 at the highest matmul
    precision, averaged over the micro-batches the step accumulates."""
    loss_fn = api.make_loss_fn(cfg, make_policy("f32"))

    @jax.jit
    def mean_loss(params, batch):
        micro = split_microbatches(batch, accum)
        return jnp.mean(jax.lax.map(lambda mb: loss_fn(params, mb)[0], micro))

    with jax.default_matmul_precision("highest"):
        return float(mean_loss(params, batch))


def peak_bytes(dev):
    """Peak device memory so far, where the backend reports it."""
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def phase_report(name: str, rec: dict, accum: int) -> str:
    timed = rec["step_s"][1:]
    return (f"{name}: {rec['batch']}x{rec['seq']} accum {accum} | compile "
            f"{rec['compile_s']:.1f} s | train-step compiles "
            f"{rec['compiles']} | tpu_custom_call {rec['pallas_kernels']} | "
            f"smoke median step {statistics.median(timed):.4f} s over "
            f"{len(timed)} steps | losses "
            f"{[round(x, 4) for x in rec['losses']]}")


def one_chip(cfg, workdir: str, failures: list) -> None:
    dev = jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    phases = bert_phases(STEPS, STEPS, global_batch_p1=PHASE1_BATCH,
                         global_batch_p2=PHASE2_BATCH)
    with StepProbe() as probe:
        state, _ = train_phases(cfg, phases, mesh, workdir=workdir, dp=True,
                                accum=ACCUM, checkpoint=False,
                                wrap_step=probe.wrap)
    del state
    for name, rec in probe.phases.items():
        print(phase_report(name, rec, ACCUM))
        if rec["compiles"] != 1:
            failures.append(f"{name}: {rec['compiles']} train-step compiles")
        if not np.all(np.isfinite(rec["losses"])):
            failures.append(f"{name}: non-finite loss {rec['losses']}")
    print(f"peak_bytes_in_use: {peak_bytes(dev)}")
    ref = fp32_reference_loss(cfg, probe.first_params, probe.first_batch,
                              ACCUM)
    got = probe.phases["phase1"]["losses"][0]
    print(f"step-1 loss {got:.6f} vs fp32 reference {ref:.6f}: |diff| "
          f"{abs(got - ref):.6f} (tolerance {REF_LOSS_TOL})")
    if not abs(got - ref) <= REF_LOSS_TOL:
        failures.append(f"step-1 loss {got} vs fp32 reference {ref}")


def four_chips(cfg, workdir: str, failures: list) -> None:
    devs = jax.devices()[:4]
    phase = bert_phases(FOUR_CHIP_STEPS, 0,
                        global_batch_p1=4 * PHASE1_BATCH)[:1]
    runs = {}
    for name, mesh_devs, accum in (("4 chips", devs, ACCUM),
                                   ("1 device", devs[:1], 4 * ACCUM)):
        mesh = make_mesh((len(mesh_devs), 1), ("data", "model"),
                         devices=mesh_devs)
        with StepProbe() as probe:
            state, _ = train_phases(cfg, phase, mesh,
                                    workdir=f"{workdir}/{len(mesh_devs)}",
                                    dp=True, accum=accum, checkpoint=False,
                                    wrap_step=probe.wrap)
        del state
        rec = runs[name] = probe.phases["phase1"]
        print(f"{name}: " + phase_report("phase1", rec, accum))
        if name == "4 chips":
            print("per-device peak_bytes_in_use: " +
                  str([peak_bytes(d) for d in devs]))
            print(f"all-reduce in the compiled DP step: {rec['all_reduce']}")
            if not rec["all_reduce"]:
                failures.append("4-chip DP step has no all-reduce")
        if rec["compiles"] != 1:
            failures.append(f"{name}: {rec['compiles']} train-step compiles")
    a, b = runs["4 chips"], runs["1 device"]
    dloss = np.abs(np.subtract(a["losses"], b["losses"]))
    dgn = np.abs(np.subtract(a["grad_norms"], b["grad_norms"])) / \
        np.abs(b["grad_norms"])
    print(f"per-step |loss diff| {dloss.tolist()} (tolerance {DP_LOSS_TOL}); "
          f"grad-norm relative diff {dgn.tolist()} "
          f"(tolerance {DP_GNORM_RTOL})")
    if not (np.all(np.isfinite(a["losses"])) and np.all(dloss <= DP_LOSS_TOL)
            and np.all(dgn <= DP_GNORM_RTOL)):
        failures.append("4-chip DP and one-device losses disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    use_compile_cache()
    print(f"device: {dev.device_kind} (platform {dev.platform}, "
          f"{len(devices)} device(s))")
    failures: list = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            four_chips(get_config("bert-large"), workdir, failures)
        else:
            one_chip(get_config("bert-large"), workdir, failures)
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
