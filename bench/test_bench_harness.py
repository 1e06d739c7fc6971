"""Rehearsal of bench/run.py on the CPU at a tiny width.

Every workload of BENCHMARK.json runs its whole path (the program's step,
loader and train_loop, the window, the reference and the checks) with the
sizes its family's ``tiny`` cuts; the result line must carry exactly the
keys the benchmark's contract names.  A four-chip cell's data-parallel mix
runs on four virtual CPU devices in a child process.  So does a second
family, entered as new files in a copy of the tree
(``bench/test_bench_families.py``).  ``main`` itself must refuse a machine
without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import pytest

from bench.test_bench_families import TOY_CELL, toy_tree

ROOT = Path(__file__).resolve().parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]

TINY_PATCH = textwrap.dedent("""
    import sys
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import peaks
    peaks.chip_peaks = lambda kind: {{"bf16_flops_per_s": 1e12}}
    from bench import run as bench_run
    bench_run.CACHE = Path({cache!r})

    def tiny(name):
        spec = bench_run.resolve(name)
        spec["config"], spec["mix"] = spec["family"].tiny(spec["config"],
                                                          spec["mix"])
        return spec
""")


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


def _run_in_child(code: str, n_devices: int, root: Path = ROOT) -> str:
    """``code`` after ``TINY_PATCH``, with the benchmark found in ``root``,
    the program in this tree's ``src`` and the run's files in a directory
    of its own, so that children running at once share none."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    with tempfile.TemporaryDirectory() as cache:
        patch = TINY_PATCH.format(root=str(root), src=str(ROOT / "src"),
                                  cache=cache)
        proc = subprocess.run([sys.executable, "-c", patch + code],
                              env=env, capture_output=True, text=True,
                              timeout=600, cwd=root)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout


ONE_CHIP = [(w["name"], traced) for w in BENCH["workloads"]
            if w["chips"] == 1 for traced in (False, True)
            if not traced or w["name"] == BENCH["workloads"][0]["name"]
            ] + [(TOY_CELL, False)]


@pytest.mark.parametrize("name,traced", ONE_CHIP)
def test_one_chip_workload_runs_at_tiny_width(name, traced, tmp_path):
    root = toy_tree(tmp_path) if name == TOY_CELL else ROOT
    out = _run_in_child(textwrap.dedent(f"""
        import jax
        spec = tiny({name!r})
        bench_run.run(spec, 2**31 + 5, 1.0, {traced}, jax.devices()[:1])
    """), 1, root)
    from bench import run as bench_run
    _check_result(_result_line(out), bench_run.resolve(name, root), traced)


FOUR_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_four_chip_mix_runs_on_virtual_devices(name):
    """A four-chip cell, on a (4, 1) data-parallel mesh of virtual CPU
    devices (a CPU trace has no device plane, so the exchange's metrics are
    left out of its line)."""
    out = _run_in_child(textwrap.dedent(f"""
        import jax
        assert len(jax.devices()) == 4
        spec = tiny({name!r})
        assert spec["mix"]["data_parallel"] == spec["chips"] == 4
        bench_run.run(spec, 7, 1.0, True, jax.devices())
    """), 4)
    from bench import run as bench_run
    _check_result(_result_line(out), bench_run.resolve(name), True)


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


def test_compile_cache_key_holds_the_metadata(tmp_path, monkeypatch):
    """An executable cached before a change of op names (named scopes) is
    not served for the program after it."""
    import jax
    from bench.run import use_compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_compilation_cache_include_metadata_in_key")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        use_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        for n, v in before.items():
            jax.config.update(n, v)


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
