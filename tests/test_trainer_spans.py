"""train_loop's profiler spans and the BERT step's named scopes.

The loop runs a dummy step under ``jax.profiler.trace`` on the CPU and the
``.xplane.pb`` it writes is read back: one ``train.step`` per step with its
step number, holding its children in order.  A 2-layer BERT train step is
compiled and its op metadata must name every program scope.
"""
import re
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import lm_batches
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train.faults import FaultInjector, FaultPlan
from repro.train.train_step import init_train_state, make_train_step_dp
from repro.train.trainer import train_loop

STEPS = 4
SCOPES = ("bert.embed", "bert.attention", "bert.ffn", "bert.heads", "lamb")


def _dummy_step(state, batch):
    s = {"w": state["w"] + batch["tokens"].astype(np.float32).mean()}
    return s, {"loss": np.float32(s["w"].sum()), "skipped": False}


def _spans(trace_dir: Path) -> list:
    """(name, start, end, stats) of every ``train.*`` host span, by start."""
    f = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(f)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("train."))
    return sorted(out, key=lambda sp: sp[1])


def _traced_loop(trace_dir: Path, **kw) -> list:
    with jax.profiler.trace(str(trace_dir)):
        train_loop(_dummy_step, {"w": np.zeros(3, np.float32)},
                   lm_batches(0, 64, 2, 4), total_steps=STEPS,
                   log_every=STEPS, retry_backoff_s=0.0, **kw)
    return _spans(trace_dir)


@pytest.fixture(scope="module")
def runs(tmp_path_factory) -> dict:
    """The spans of three traced loops: plain, with step 2's first attempt
    failing, and checkpointing every 2 steps."""
    d = tmp_path_factory.mktemp("spans")
    return {
        "plain": _traced_loop(d / "plain"),
        "retried": _traced_loop(d / "retried", faults=FaultInjector(
            FaultPlan(fail_at=2, fail_count=1))),
        "checkpointed": _traced_loop(d / "ckpt", ckpt_dir=str(d / "ck"),
                                     ckpt_every=2),
    }


def _steps(spans) -> list:
    return [sp for sp in spans if sp[0] == "train.step"]


def _children(spans, step) -> list:
    return [sp for sp in spans if sp[0] != "train.step"
            and step[1] <= sp[1] and sp[2] <= step[2]]


@pytest.mark.parametrize("run", ["plain", "retried", "checkpointed"])
def test_one_step_span_per_step(runs, run):
    steps = _steps(runs[run])
    assert [int(sp[3]["step_num"]) for sp in steps] == \
        list(range(1, STEPS + 1))
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))


PLAIN = ["train.data", "train.dispatch", "train.sync"]


@pytest.mark.parametrize("run, step_num, want", [
    ("plain", 1, PLAIN),
    ("plain", STEPS, PLAIN),
    ("retried", 1, PLAIN),
    ("retried", 2, ["train.data", "train.dispatch", "train.dispatch",
                    "train.sync"]),
    ("checkpointed", 1, PLAIN),
    ("checkpointed", 2, PLAIN + ["train.checkpoint"]),
])
def test_children_nest_in_order(runs, run, step_num, want):
    step = _steps(runs[run])[step_num - 1]
    kids = _children(runs[run], step)
    assert [sp[0] for sp in kids] == want
    assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))


@pytest.mark.parametrize("run, step_num, attempts", [
    ("plain", 2, [1]), ("retried", 2, [1, 2]), ("retried", 3, [1])])
def test_dispatch_span_per_attempt(runs, run, step_num, attempts):
    step = _steps(runs[run])[step_num - 1]
    got = [int(sp[3]["attempt"]) for sp in _children(runs[run], step)
           if sp[0] == "train.dispatch"]
    assert got == attempts


def test_every_span_lies_in_a_step_but_the_final_save(runs):
    spans = runs["checkpointed"]
    loose = [sp[0] for sp in spans if sp[0] != "train.step" and not any(
        st[1] <= sp[1] and sp[2] <= st[2] for st in _steps(spans))]
    assert loose == ["train.checkpoint"]
    assert spans[-1][0] == "train.checkpoint"


@pytest.fixture(scope="module")
def bert_step_hlo() -> str:
    """The compiled text of a 2-layer BERT step: DP psum, bf16, LAMB,
    accumulation 2.  A persistent compilation cache leaves op metadata out
    of its key, so an executable it holds may carry older op names; the
    key takes them in here."""
    cfg = smoke_variant(get_config("bert-large"), d_model=64, n_blocks=2)
    tcfg = TrainConfig(precision="bf16", accum_steps=2, optimizer="lamb",
                       collective_strategy="psum", total_steps=10,
                       warmup_steps=2)
    shape = InputShape("scopes", 32, 4, "train")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    step, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
    state = jax.eval_shape(lambda k: init_train_state(
        api.init_params(k, cfg)[0], make_policy("bf16"), tcfg),
        jax.random.PRNGKey(0))
    batch = api.train_batch_struct(cfg, shape)
    key = "jax_compilation_cache_include_metadata_in_key"
    keyed = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return step.lower(state, batch).compile().as_text()
    finally:
        jax.config.update(key, keyed)


@pytest.mark.parametrize("scope", SCOPES)
def test_bert_step_names_scope(bert_step_hlo, scope):
    """Forward ops name it as ``jvp(<scope>)`` or ``.../<scope>/...``,
    backward ops as ``transpose(jvp(<scope>))``."""
    named = re.compile(rf"(?<![\w.]){re.escape(scope)}(?![\w.])")
    ops = [n for n in re.findall(r'op_name="([^"]*)"', bert_step_hlo)
           if named.search(n)]
    assert ops, f"no op of the compiled step carries {scope!r}"
    assert any("transpose(" in n for n in ops) != (scope == "lamb")
