"""``correct`` must come out false when the timed path is broken.

Each test drives a whole run of bench/run.py at a tiny width on the CPU,
past its look for a chip, with one fault planted under the timed path:
a step that hands back its state unchanged and a step that takes the mean
over half of its batch, on one device and on the four-chip cell's four
virtual devices, and there also a data-parallel step whose gradient
exchange is left out.  The control, the reference computed
in fp8 in the program's place, must stand apart from the sound program.
"""
import json
import textwrap

import pytest

from bench.test_bench_harness import _result_line, _run_in_child

FAULTS = {
    "state_unchanged": """
        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
    """,
    "half_batch": """
        import numpy as np
        def broken(state, batch):
            half = {k: v[:len(v) // 2] for k, v in batch.items()}
            return step(state, {k: np.concatenate([v, v])
                                for k, v in half.items()})
    """,
}

PLANT = """
import dataclasses
from bench import cell as cell_mod
real = cell_mod.make_train_step_dp

def planted(cfg, tcfg, mesh, shape):
    step, struct = real(cfg, {tcfg}, mesh, shape)
{broken}
    return broken, struct

cell_mod.make_train_step_dp = planted
"""

RUN = """
    import jax
    spec = tiny({name!r})
    bench_run.run(spec, 11, 1.0, False, jax.devices()[:spec["chips"]])
"""


def _plant(broken: str, tcfg: str = "tcfg") -> str:
    body = textwrap.indent(textwrap.dedent(broken), " " * 4)
    return PLANT.format(tcfg=tcfg, broken=body)


def _failed_checks(out: str) -> list:
    res = _result_line(out)
    assert res["correct"] is False
    return [k for k, c in res["checks"].items()
            if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_step_is_not_correct(fault):
    out = _run_in_child(_plant(FAULTS[fault]) + textwrap.dedent(
        RUN.format(name="bert-large.p1-s128")), 1)
    assert _failed_checks(out)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_four_chip_step_is_not_correct(fault):
    out = _run_in_child(_plant(FAULTS[fault]) + textwrap.dedent(
        RUN.format(name="bert-large.p1-s128-dp4")), 4)
    assert _failed_checks(out)


def test_left_out_exchange_is_not_correct():
    local = 'dataclasses.replace(tcfg, collective_strategy="local")'
    broken = """
        broken = step
    """
    out = _run_in_child(_plant(broken, local) + textwrap.dedent(
        RUN.format(name="bert-large.p1-s128-dp4")), 4)
    assert _failed_checks(out)


def test_the_fp8_control_is_told_apart():
    """The control, the reference in fp8 put in the program's place, against
    the bf16 program, both against the fp32 reference.  At a size a test
    run holds, four layers of width 256 at the cell's sequence length, the
    cell's limits (set from chip runs at published widths) do not apply, so
    the test asks what they were set from: on ``grad_err``, the number that
    tells the two apart, the smallest control reading at least three times
    the largest sound one, and each reading judged by the cell's limits."""
    out = _run_in_child(textwrap.dedent("""
        import json, jax
        from bench import control
        from bench.cell import TrainCell
        spec = bench_run.resolve("bert-large.p1-s128")
        spec["config"].update(n_layers=4, d_model=256, n_heads=4,
                              head_dim=64, d_ff=1024, vocab_size=4096,
                              max_position=128)
        spec["mix"].update(batch_per_chip=16, rows_per_chip=256)
        cell = TrainCell(spec["family"], spec["config"], spec["mix"],
                         jax.devices()[:1],
                         bench_run.CACHE / "run" / "control-test")
        for seed in (3, 4, 5):
            print(json.dumps(control.readings(
                cell, seed, {"program", "control"}, spec["limits"])))
    """), 1)
    rows = [json.loads(line) for line in out.strip().splitlines()[-3:]]
    sound = max(r["program"]["grad_err"] for r in rows)
    assert min(r["control"]["grad_err"] for r in rows) >= 3 * sound, rows
    assert all(isinstance(r[k]["correct"], bool) for r in rows
               for k in ("program", "control"))
