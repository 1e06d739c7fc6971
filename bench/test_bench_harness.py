"""Rehearsal of bench/run.py on the CPU at a tiny width.

Every workload of BENCHMARK.json runs its whole path (the program's step,
loader and train_loop, the window, the reference and the checks) with the
sizes cut here in the test; the result line must carry exactly the keys
the benchmark's contract names.  The data-parallel mix runs on four
virtual CPU devices in a child process.  ``main`` itself must refuse a machine
without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]

TINY_PATCH = textwrap.dedent("""
    import sys
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import flops
    flops.chip_peaks = lambda kind: {{"bf16_flops_per_s": 1e12}}
    from bench import run as bench_run

    def tiny(name, data_parallel=None):
        spec = bench_run.resolve(name)
        if data_parallel:           # a data-parallel mesh no cell runs yet
            spec["mix"]["data_parallel"] = spec["chips"] = data_parallel
        spec["config"].update(n_layers=2, d_model=64, n_heads=2,
                              head_dim=32, d_ff=128, vocab_size=512,
                              max_position=128)
        spec["mix"].update(seq_len=32, n_predictions=5, batch_per_chip=8,
                           rows_per_chip=64)
        return spec
""").format(root=str(ROOT), src=str(ROOT / "src"))


def _result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def _check_result(res: dict, spec: dict, traced: bool) -> None:
    from bench import run as bench_run
    keys = RESULT_KEYS[:5] + (["breakdown"] if traced else []) + ["checks"]
    assert list(res) == keys
    assert res["attempted"] >= 10 and res["failed"] == 0
    assert set(res["checks"]) == {k for k, v in spec["limits"].items()
                                  if k in bench_run.CHECKS and v is not None}
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] == spec["chips"]
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    names = {m["name"] for m in (spec["per_layer"] if traced
                                 else spec["end_to_end"])}
    assert set(res["metrics"]) <= names
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert {"mfu", "compile_s"} <= set(res["metrics"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == names
    assert all(set(m) == {"value", "unit"} for m in res["metrics"].values())
    assert res["correct"] is True, res["checks"]


def _run_in_child(code: str, n_devices: int) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    proc = subprocess.run([sys.executable, "-c", TINY_PATCH + code],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


ONE_CHIP = [(w["name"], traced) for w in BENCH["workloads"]
            if w["chips"] == 1 for traced in (False, True)
            if not traced or w["name"] == BENCH["workloads"][0]["name"]]


@pytest.mark.parametrize("name,traced", ONE_CHIP)
def test_one_chip_workload_runs_at_tiny_width(name, traced):
    out = _run_in_child(textwrap.dedent(f"""
        import jax
        spec = tiny({name!r})
        bench_run.run(spec, 2**31 + 5, 1.0, {traced}, jax.devices()[:1])
    """), 1)
    from bench import run as bench_run
    _check_result(_result_line(out), bench_run.resolve(name), traced)


def test_four_chip_mix_runs_on_virtual_devices():
    """Phase 1 on a (4, 1) data-parallel mesh of virtual CPU devices: the
    path of a four-chip cell still to come (PERF.md, Open questions)."""
    out = _run_in_child(textwrap.dedent("""
        import jax
        assert len(jax.devices()) == 4
        spec = tiny("bert-large.p1-s128", data_parallel=4)
        bench_run.run(spec, 7, 1.0, True, jax.devices())
    """), 4)
    from bench import run as bench_run
    spec = dict(bench_run.resolve("bert-large.p1-s128"), chips=4)
    _check_result(_result_line(out), spec, True)


def test_judge_leaves_out_a_number_with_no_limit():
    from bench.run import judge
    found = {"loss_gap": 0.5, "grad_gap": 0.1, "grad_err": 0.01,
             "change_gap": 0.01, "rows_missing": 0}
    limits = {"loss_gap": None, "grad_gap": 0.2, "grad_err": 0.02,
              "change_gap": 0.02, "rows_missing": 0}
    checks, correct = judge(found, limits)
    assert correct and "loss_gap" not in checks
    assert checks["grad_err"] == {"value": 0.01, "limit": 0.02}
    assert not judge(dict(found, grad_err=0.03), limits)[1]
    assert not judge(found, limits, failed=1)[1]


def _cli(cwd: Path, **env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_main_refuses_a_machine_without_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _cli(tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
