"""chip_smoke.py's checks, rehearsed on the CPU at a reduced width.

The script itself refuses to run without a TPU; these tests drive its
one-chip and four-chip paths with a smoke-width BERT so that a wrong path,
a second compile of the train step or a broken reference comparison shows
up here rather than on the chip.
"""
import importlib.util
import subprocess
import sys

import pytest

from repro.configs import get_config, smoke_variant

from conftest import REPO, run_multidevice


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"})
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_path_at_smoke_width(tmp_path, capsys):
    cs = _chip_smoke()
    cfg = smoke_variant(get_config("bert-large"), d_model=64, n_blocks=1)
    failures = []
    cs.one_chip(cfg, str(tmp_path), failures)
    out = capsys.readouterr().out
    assert failures == [], out
    assert "phase1: 32x128" in out and "phase2: 8x512" in out
    assert out.count("train-step compiles 1 ") == 2


def test_four_chip_path_at_smoke_width(tmp_path):
    out = run_multidevice(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(REPO / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_config, smoke_variant
        cfg = smoke_variant(get_config("bert-large"), d_model=64, n_blocks=1)
        failures = []
        cs.four_chips(cfg, {str(tmp_path)!r}, failures)
        print("FAILURES", failures)
    """, n_devices=4)
    assert "FAILURES []" in out, out
    assert "all-reduce in the compiled DP step: True" in out
