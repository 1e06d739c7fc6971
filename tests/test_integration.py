"""End-to-end behaviour: BERT pretraining convergence, the paper's Fig 8
optimized-vs-nonoptimized equivalence, checkpoint resume."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import ShardedLoader, prepare_bert_data
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.sharding import make_rules
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.train_step import (init_train_state, make_train_step_dp,
                                    make_train_step_gspmd)


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh((1, 1), ("data", "model"))


def _bert_setup(tmp_path, seq_len=64, batch=8):
    cfg = smoke_variant(get_config("bert-large"), d_model=128)
    tok, _ = prepare_bert_data(str(tmp_path), seq_len=seq_len, n_docs=60,
                               vocab_size=cfg.vocab_size, n_shards=2)
    loader = ShardedLoader(str(tmp_path), 0, 1, batch=batch)
    return cfg, loader


def test_bert_pretraining_loss_decreases(tmp_path, mesh):
    """Real pipeline -> shards -> loader -> LAMB + AMP + accumulation:
    loss must fall substantially over 30 steps."""
    cfg, loader = _bert_setup(tmp_path, batch=16)
    tcfg = TrainConfig(precision="bf16", accum_steps=2, optimizer="lamb",
                       learning_rate=3e-3, total_steps=80, warmup_steps=5)
    shapes, specs = api.abstract_params(cfg)
    shape = InputShape("t", 64, 16, "train")
    step, _ = make_train_step_gspmd(cfg, tcfg, mesh, make_rules(), specs,
                                    shapes, shape)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, make_policy("bf16"), tcfg)
    it = iter(loader)
    losses = []
    for i in range(70):
        state, m = step(state, next(it))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_fig8_optimized_equals_nonoptimized(tmp_path, mesh):
    """Paper Fig 8: the full optimization stack (fp16+scaling, accumulation,
    LAMB fused math) tracks the non-optimized fp32 loss curve."""
    cfg, loader = _bert_setup(tmp_path)
    shape = InputShape("t", 64, 8, "train")
    shapes, specs = api.abstract_params(cfg)
    it = iter(loader)
    fixed_batches = [next(it) for _ in range(15)]  # identical data per run

    curves = {}
    for name, tcfg in {
        "baseline_f32": TrainConfig(precision="f32", accum_steps=1,
                                    learning_rate=2e-4, total_steps=20,
                                    warmup_steps=2),
        "optimized_f16_accum": TrainConfig(precision="f16", accum_steps=4,
                                           learning_rate=2e-4,
                                           total_steps=20, warmup_steps=2),
    }.items():
        step, _ = make_train_step_gspmd(cfg, tcfg, mesh, make_rules(),
                                        specs, shapes, shape)
        # fresh params each run: the train step donates its state buffers
        params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params, make_policy(tcfg.precision), tcfg)
        losses = []
        for b in fixed_batches:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        curves[name] = losses
    base = np.asarray(curves["baseline_f32"])
    opt = np.asarray(curves["optimized_f16_accum"])
    # identical data order => curves must track within dtype noise
    assert np.max(np.abs(base - opt)) < 0.08, (base, opt)


def test_checkpoint_roundtrip_resume(tmp_path, mesh):
    cfg = smoke_variant(get_config("deepseek-7b"), d_model=128)
    tcfg = TrainConfig(precision="bf16", total_steps=10, warmup_steps=1)
    shape = InputShape("t", 32, 4, "train")
    shapes, specs = api.abstract_params(cfg)
    step, _ = make_train_step_gspmd(cfg, tcfg, mesh, make_rules(), specs,
                                    shapes, shape)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, make_policy("bf16"), tcfg)
    batch = api.make_synth_batch(jax.random.PRNGKey(1), cfg, shape)
    state, _ = step(state, batch)
    save_checkpoint(str(tmp_path / "ck"), 1, state)
    restored, at = restore_checkpoint(str(tmp_path / "ck"), state)
    assert at == 1
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # stepping the restored state must give the same next state
    s1, m1 = step(state, batch)
    s2, m2 = step(restored, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)


def test_moe_router_aux_decreases_imbalance(mesh):
    """Training with the load-balance loss keeps expert usage spread (the
    MoE substrate works as a trainable system, not a stub)."""
    import dataclasses
    cfg = smoke_variant(get_config("qwen3-moe-30b-a3b"), d_model=64)
    cfg = dataclasses.replace(cfg, router_aux_coef=0.05)
    tcfg = TrainConfig(precision="f32", total_steps=30, warmup_steps=2,
                       learning_rate=1e-3, moe_impl="dense")
    shape = InputShape("t", 32, 8, "train")
    shapes, specs = api.abstract_params(cfg)
    step, _ = make_train_step_gspmd(cfg, tcfg, mesh, make_rules(), specs,
                                    shapes, shape)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, make_policy("f32"), tcfg)
    batch = api.make_synth_batch(jax.random.PRNGKey(1), cfg, shape)
    auxes = []
    for i in range(20):
        state, m = step(state, batch)
        auxes.append(float(m["router_aux"]))
    # aux ~1.0 = balanced; must not blow up and should not exceed start
    assert auxes[-1] < auxes[0] * 1.5
    assert all(np.isfinite(a) for a in auxes)


@pytest.mark.parametrize("dp", [False, True], ids=["gspmd", "dp"])
def test_train_phases_compiles_each_phase_once(tmp_path, mesh, dp):
    """examples/pretrain_bert.py's phase function: both phases train, each
    phase compiles its train step exactly once (an initial state placed
    unlike the step's outputs used to compile it again at step 2)."""
    import importlib.util
    from collections import Counter

    from jax import monitoring

    from conftest import REPO
    from repro.train.phases import bert_phases

    spec = importlib.util.spec_from_file_location(
        "pretrain_bert", REPO / "examples" / "pretrain_bert.py")
    pretrain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pretrain)

    compiles, current = Counter(), {}

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration" and \
                kw.get("fun_name") == "jit(train_step)":
            compiles[current["phase"]] += 1

    def wrap(phase, step):
        current["phase"] = phase.name
        return step

    cfg = smoke_variant(get_config("bert-large"), d_model=64)
    phases = bert_phases(3, 2, global_batch_p1=8, global_batch_p2=8,
                         learning_rate=2e-3)
    monitoring.register_event_duration_secs_listener(on_compile)
    try:
        _, hist = pretrain.train_phases(cfg, phases, mesh,
                                        workdir=str(tmp_path), dp=dp,
                                        accum=2, wrap_step=wrap)
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
    assert compiles == {"phase1": 1, "phase2": 1}
    assert [h[-1]["step"] for h in hist.values()] == [3, 2]
    assert all(np.isfinite(m["loss"]) for h in hist.values() for m in h)
