"""train_loop's lagged non-finite supervision.

Step k's loss and skip flag are read only after step k+1 is dispatched; the
loop drains before each log, each checkpoint and at the end of the call.
A step whose metrics note when they are read shows the order.  The skip
counts, the budget's error and the losses must be those of a loop that
reads every step at once (``log_every=1`` drains every step, which is that
order); the expected counts were taken from the loop before the lag.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import lm_batches
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train import trainer
from repro.train.checkpoint import (latest_step, load_manifest,
                                    restore_checkpoint)
from repro.train.faults import FaultInjector, FaultPlan
from repro.train.train_step import init_train_state, make_train_step_dp
from repro.train.trainer import NonFiniteBudgetError, train_loop

STEPS = 8


class Recorded:
    """A metric value that notes on ``tape`` each time it is read."""

    def __init__(self, tape, step, value):
        self.tape, self.step, self.value = tape, step, value

    def __array__(self, dtype=None, copy=None):
        self.tape.append(("read", self.step))
        return np.asarray(self.value, dtype)


def _state():
    return {"w": np.zeros(3, np.float32), "n": np.zeros(1, np.int32)}


def _recording_step(tape, skip=()):
    """Counts the steps applied to the state in ``n``; steps in ``skip``
    report themselves skipped and leave ``w`` alone, as AMP's skip does."""
    def step(state, batch):
        n = state["n"] + 1
        tape.append(("dispatch", int(n[0])))
        skipped = int(n[0]) in skip
        w = state["w"] if skipped else \
            state["w"] + batch["tokens"].astype(np.float32).mean()
        loss = np.float32("nan") if skipped else np.float32(w.sum())
        return {"w": w, "n": n}, {"loss": Recorded(tape, int(n[0]), loss),
                                  "skipped": Recorded(tape, int(n[0]),
                                                      skipped)}
    return step


def _run(tape, monkeypatch=None, ckpt_dir=None, **kw):
    """A recorded loop of STEPS steps; logs and saves go on the tape."""
    if monkeypatch is not None:
        save = trainer.save_checkpoint

        def noted_save(d, step, *a, **k):
            tape.append(("save", step))
            return save(d, step, *a, **k)
        monkeypatch.setattr(trainer, "save_checkpoint", noted_save)
    kw.setdefault("total_steps", STEPS)
    _, hist = train_loop(_recording_step(tape), _state(),
                         lm_batches(0, 64, 2, 4), ckpt_dir=ckpt_dir,
                         metrics_hook=lambda m: tape.append(("log",
                                                             m["step"])),
                         **kw)
    tape.append(("return", STEPS))
    return hist


def _first(tape, event):
    return tape.index(event)


def test_next_step_dispatched_before_a_step_is_read():
    tape = []
    _run(tape, log_every=STEPS)
    for n in range(1, STEPS):
        reads = [i for i, ev in enumerate(tape) if ev == ("read", n)]
        assert len(reads) == 2          # the loss and the flag, once each
        assert _first(tape, ("dispatch", n + 1)) < reads[0]
        if n + 2 <= STEPS:
            assert reads[-1] < _first(tape, ("dispatch", n + 2))


@pytest.mark.parametrize("log_every, ckpt_every, drains", [
    (STEPS, None, [1]),
    (1, None, [1] * STEPS),
    (4, 3, [2, 2]),
    (4, 4, [1, 1]),
])
def test_drains_before_log_checkpoint_and_end(tmp_path, monkeypatch,
                                              log_every, ckpt_every, drains):
    tape = []
    kw = dict(ckpt_dir=str(tmp_path), ckpt_every=ckpt_every) \
        if ckpt_every else {}
    hist = _run(tape, monkeypatch, log_every=log_every, **kw)
    assert [h["drains"] for h in hist] == drains
    stops = [(i, ev[1]) for i, ev in enumerate(tape)
             if ev[0] in ("log", "save", "return")]
    assert {ev[0] for ev in tape} >= {"log", "return"}
    for i, upto in stops:
        read = {ev[1] for ev in tape[:i] if ev[0] == "read"}
        assert read >= set(range(1, upto + 1)), tape[i]
    # every step that is not drained is read behind the next dispatch
    drained = {s for _, s in stops}
    for n in set(range(1, STEPS + 1)) - drained:
        assert _first(tape, ("dispatch", n + 1)) < _first(tape, ("read", n))


@pytest.mark.parametrize("log_every", [1, 3, 9])
def test_budget_breach_names_the_step_that_broke_it(log_every):
    inj = FaultInjector(FaultPlan(nan_at=3, nan_count=5))
    with pytest.raises(NonFiniteBudgetError) as err:
        train_loop(_recording_step([]), _state(), lm_batches(0, 64, 2, 4),
                   total_steps=9, log_every=log_every,
                   max_consecutive_skips=2, faults=inj)
    assert str(err.value) == ("3 consecutive non-finite/skipped steps at "
                              "step 5 (budget 2): aborting")


@pytest.mark.parametrize("log_every, saved_at", [(1, 5), (9, 6)])
def test_emergency_checkpoint_holds_the_steps_it_is_numbered_by(
        tmp_path, log_every, saved_at):
    """Steps 3..7 report a skip; the budget of 2 breaks at step 5.  Drained
    every step, the loop stops after step 5; lagged, step 6 is already
    applied when step 5 is read."""
    d = str(tmp_path)
    with pytest.raises(NonFiniteBudgetError, match="at step 5 "):
        train_loop(_recording_step([], skip=range(3, 8)), _state(),
                   lm_batches(0, 64, 2, 4), total_steps=9,
                   log_every=log_every, max_consecutive_skips=2,
                   ckpt_dir=d, ckpt_every=100)
    assert latest_step(d) == saved_at
    assert load_manifest(d, saved_at)["extra"]["emergency"] is True
    state, step = restore_checkpoint(d, _state())
    assert step == int(np.asarray(state["n"])[0]) == saved_at


# (plan, budget, total steps, (total_skips, consecutive_skips) per step as
# the loop before the lag logged them with log_every=1)
PLANS = {
    "nan": (FaultPlan(nan_at=2, nan_count=2), 5, 6,
            [(0, 0), (1, 1), (2, 2), (2, 0), (2, 0), (2, 0)]),
    "fail": (FaultPlan(fail_at=2, fail_count=2), 25, 4, [(0, 0)] * 4),
    "slow": (FaultPlan(slow_at=5, slow_s=0.3), 25, 6, [(0, 0)] * 6),
}


@pytest.mark.parametrize("log_every", [1, 2, 5])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_skip_counts_match_the_unlagged_loop(plan, log_every):
    fault_plan, budget, total, want = PLANS[plan]
    _, hist = train_loop(
        _recording_step([]), _state(), lm_batches(0, 64, 2, 4),
        total_steps=total, log_every=log_every,
        max_consecutive_skips=budget, faults=FaultInjector(fault_plan),
        retry_backoff_s=0.0)
    logged = [s for s in range(1, total + 1)
              if s % log_every == 0 or s == total]
    assert [h["step"] for h in hist] == logged
    assert [(h["total_skips"], h["consecutive_skips"]) for h in hist] == \
        [want[s - 1] for s in logged]


BERT_STEPS = 4


@pytest.fixture(scope="module")
def bert_runs() -> dict:
    """A 2-layer BERT DP step (bf16, LAMB, accumulation 2) through the
    loop, drained every step and drained only at the end: each step's
    metrics on the host, and the final state."""
    cfg = smoke_variant(get_config("bert-large"), d_model=64, n_blocks=2)
    tcfg = TrainConfig(precision="bf16", accum_steps=2, optimizer="lamb",
                       collective_strategy="psum", total_steps=10,
                       warmup_steps=2)
    shape = InputShape("lag", 32, 4, "train")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    step, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
    state0 = init_train_state(api.init_params(jax.random.PRNGKey(0), cfg)[0],
                              make_policy("bf16"), tcfg)
    batches = [api.make_synth_batch(jax.random.PRNGKey(i + 1), cfg, shape)
               for i in range(BERT_STEPS)]
    runs = {}
    for log_every in (1, BERT_STEPS):
        kept = []

        def keep(state, batch):
            state, metrics = step(state, batch)
            kept.append(metrics)
            return state, metrics
        state, _ = train_loop(keep, state0, iter(batches),
                              total_steps=BERT_STEPS, log_every=log_every)
        runs[log_every] = (jax.device_get(kept), jax.device_get(state))
    return runs


@pytest.mark.parametrize("name", ["loss", "mlm_loss", "nsp_loss"])
def test_bert_losses_equal_to_the_bit_drained_or_lagged(bert_runs, name):
    drained, lagged = (bert_runs[k][0] for k in (1, BERT_STEPS))
    a = np.asarray([m[name] for m in drained])
    b = np.asarray([m[name] for m in lagged])
    assert np.all(np.isfinite(a))
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_bert_final_state_equal_to_the_bit_drained_or_lagged(bert_runs):
    drained, lagged = (bert_runs[k][1] for k in (1, BERT_STEPS))
    for x, y in zip(jax.tree_util.tree_leaves(drained),
                    jax.tree_util.tree_leaves(lagged)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
