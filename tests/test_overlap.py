"""Overlapped bucketed gradient exchange (drain schedule) + comm autotuner.

Single-process tests cover the autotune search loop (grid validity, the
successive-halving race) and the schedule-aware fig3 roofline; subprocess
tests (forced host devices) cover the bit-exactness contract: the
overlapped drain schedule must produce BIT-IDENTICAL losses to the serial
psum path across accumulation depths and bucket boundaries, compose with
int8 + error feedback, and survive a checkpoint/restore round trip.
"""
import sys
from pathlib import Path

import pytest

from conftest import REPO, run_multidevice
from repro.tune.autotune import (DEFAULT_SPACE, make_grid,
                                 successive_halving, tokens_per_s)

sys.path.insert(0, str(REPO))  # benchmarks.* (namespace package at repo root)

from benchmarks.fig3_weak_scaling import (BWD_FRAC, COMPUTE_1,  # noqa: E402
                                          drain_overlap_window, eff_from)


# ---------------------------------------------------------------------------
# Autotuner search loop (no devices needed)
# ---------------------------------------------------------------------------

def test_make_grid_filters_and_dedupes():
    grid = make_grid(devices=4, global_batch=32)
    # every candidate is valid: accum divides per-device batch (8)
    assert all(8 % c["accum_steps"] == 0 for c in grid)
    # bucket-size dedup: serial uncompressed psum ignores bucket_bytes, so
    # only ONE bucket point survives for that cell
    serial_psum_none = [c for c in grid
                       if c["strategy"] == "psum" and not c["overlap"]
                       and c["compression"] == "none"]
    assert len(serial_psum_none) == len(DEFAULT_SPACE["accum_steps"])
    # ... but overlapped cells keep every bucket point (packing granularity
    # is the thing being tuned)
    ov_psum_none = [c for c in grid
                    if c["strategy"] == "psum" and c["overlap"]
                    and c["compression"] == "none"]
    assert len(ov_psum_none) == (len(DEFAULT_SPACE["bucket_bytes"]) *
                                 len(DEFAULT_SPACE["accum_steps"]))
    # no duplicates overall
    keys = [tuple(sorted(c.items())) for c in grid]
    assert len(keys) == len(set(keys))


def test_make_grid_drops_hierarchical_on_small_meshes():
    assert any(c["strategy"] == "hierarchical"
               for c in make_grid(devices=4))
    assert not any(c["strategy"] == "hierarchical"
                   for c in make_grid(devices=2))
    assert not any(c["strategy"] == "hierarchical"
                   for c in make_grid(devices=5))


def test_successive_halving_races_and_records_failures():
    space = {"bucket_bytes": [64], "accum_steps": [1],
             "strategy": ["psum", "ring", "bucketed"],
             "compression": ["none"], "overlap": [False, True]}
    grid = make_grid(space, devices=4, global_batch=32)
    # synthetic cost model: overlap is fastest, ring errors out
    calls = []

    def measure(cand, iters):
        calls.append((cand["strategy"], cand["overlap"], iters))
        if cand["strategy"] == "ring":
            raise ValueError("boom")
        base = 100.0 if cand["overlap"] else 80.0
        return base + (5.0 if cand["strategy"] == "bucketed" else 0.0)

    best, trials = successive_halving(grid, measure, iters0=2,
                                      keep_frac=0.5, max_rounds=3)
    assert best["strategy"] == "bucketed" and best["overlap"] is True
    assert best["tokens_per_s"] == 105.0
    # failed candidates are recorded with the error and never re-raced
    errs = [t for t in trials if "error" in t]
    assert errs and all("boom" in t["error"] for t in errs)
    assert all(t["round"] == 0 for t in errs)
    # the budget doubles each surviving round
    assert {it for _, _, it in calls} == {2, 4, 8}
    # the trial table shows the whole race, round by round
    assert {t["round"] for t in trials} == {0, 1, 2}


def test_successive_halving_all_failures_raises():
    def measure(cand, iters):
        raise RuntimeError("nope")
    with pytest.raises(RuntimeError, match="every candidate failed"):
        successive_halving([{"bucket_bytes": 1, "accum_steps": 1,
                             "strategy": "psum", "compression": "none",
                             "overlap": False}], measure)


def test_tokens_per_s():
    assert tokens_per_s(0.5, global_batch=32, seq=128) == 32 * 128 / 0.5


# ---------------------------------------------------------------------------
# Schedule-aware roofline (fig3 overlap term)
# ---------------------------------------------------------------------------

def test_eff_from_overlap_window():
    comm, compute = 1.0, 2.0
    serial = eff_from(comm, compute, overlap_window=0.0)
    legacy = eff_from(comm, compute)             # 0.3 * compute window
    hidden = eff_from(comm, compute, overlap_window=comm)
    assert serial == compute / (compute + comm)  # everything exposed
    assert serial < legacy < hidden == 1.0       # window monotone in eff
    # window larger than comm cannot push efficiency past 1
    assert eff_from(comm, compute, overlap_window=10 * comm) == 1.0


def test_drain_overlap_window_is_one_backward_pass():
    assert drain_overlap_window() == pytest.approx(BWD_FRAC * COMPUTE_1)
    assert drain_overlap_window(3.0) == pytest.approx(2.0)
    # the window does NOT scale with accumulation: only the LAST
    # micro-batch's backward can hide exchange under the drain schedule
    assert drain_overlap_window(COMPUTE_1) == drain_overlap_window()


# ---------------------------------------------------------------------------
# Multi-device: bit-exactness of the drain schedule
# ---------------------------------------------------------------------------

def test_overlap_bit_identical_to_serial_psum_across_accum():
    """5-step losses bit-match serial psum at accum 1/2/4, plus an uneven
    (prime) bucket size that forces leaves to straddle bucket boundaries."""
    out = run_multidevice("""
        import jax, numpy as np
        from repro.configs import get_config, smoke_variant
        from repro.configs.base import InputShape, TrainConfig
        from repro.core.amp import make_policy
        from repro.launch.mesh import make_mesh
        from repro.models import api
        from repro.train.train_step import (init_train_state,
                                            make_train_step_dp)
        assert len(jax.devices()) == 4
        cfg = smoke_variant(get_config("bert-large"), d_model=64)
        shape = InputShape("t", 32, 16, "train")
        params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
        batches = [api.make_synth_batch(jax.random.PRNGKey(i), cfg, shape)
                   for i in range(5)]
        def run(accum, overlap, bucket_bytes=1 << 16):
            tcfg = TrainConfig(precision="f32", accum_steps=accum,
                               collective_strategy="psum",
                               overlap_exchange=overlap, total_steps=50,
                               warmup_steps=2, bucket_bytes=bucket_bytes)
            step, _ = make_train_step_dp(cfg, tcfg,
                                         make_mesh((4,), ("data",)), shape)
            state = init_train_state(params, make_policy("f32"), tcfg,
                                     world=4)
            losses = []
            for b in batches:
                state, m = step(state, b)
                losses.append(float(np.asarray(m["loss"])))
            return losses
        for accum in (1, 2, 4):
            ref, got = run(accum, False), run(accum, True)
            assert got == ref, (accum, got, ref)
            print(f"accum={accum} bit-identical")
        assert run(2, True, bucket_bytes=50021) == run(2, False)
        print("uneven buckets bit-identical")
        print("OK")
    """, n_devices=4, timeout=900)
    assert "OK" in out


def test_overlap_composes_with_int8_error_feedback_and_resume():
    """Overlapped drain + int8 wire + error feedback: bit-identical to the
    serial compressed path, and 2 steps + checkpoint/restore + 2 steps
    matches 4 straight steps bit for bit (PR 7 exact-resume contract)."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_config, smoke_variant
        from repro.configs.base import InputShape, TrainConfig
        from repro.core.amp import make_policy
        from repro.launch.mesh import make_mesh
        from repro.models import api
        from repro.train.checkpoint import (restore_checkpoint,
                                            save_checkpoint)
        from repro.train.train_step import (init_train_state,
                                            make_train_step_dp)
        cfg = smoke_variant(get_config("bert-large"), d_model=64)
        shape = InputShape("t", 32, 8, "train")
        def make(overlap):
            tcfg = TrainConfig(precision="f32", accum_steps=2,
                               total_steps=10, warmup_steps=1,
                               collective_strategy="psum",
                               grad_compression="int8",
                               overlap_exchange=overlap,
                               bucket_bytes=1 << 16)
            step, _ = make_train_step_dp(cfg, tcfg,
                                         make_mesh((2,), ("data",)), shape)
            return step, tcfg
        params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
        batches = [api.make_synth_batch(jax.random.PRNGKey(i), cfg, shape)
                   for i in range(4)]
        pol = make_policy("f32")

        # 1) overlapped compressed losses == serial compressed losses
        def run(step, tcfg):
            state = init_train_state(params, pol, tcfg, world=2)
            assert state.err is not None
            losses = []
            for b in batches:
                state, m = step(state, b)
                losses.append(float(np.asarray(m["loss"])))
            return state, losses
        step_s, tcfg_s = make(False)
        step_o, tcfg_o = make(True)
        _, ref = run(step_s, tcfg_s)
        straight, got = run(step_o, tcfg_o)
        assert got == ref, (got, ref)
        print("int8 overlap == int8 serial (bit-identical)")

        # 2) crash -> resume bit-identity with the err buffer checkpointed
        state = init_train_state(params, pol, tcfg_o, world=2)
        for b in batches[:2]:
            state, _ = step_o(state, b)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 2, state)
        restored, at = restore_checkpoint(d, jax.tree_util.tree_map(
            jnp.zeros_like, state))
        assert at == 2
        for b in batches[2:]:
            restored, _ = step_o(restored, b)
        for a, b in zip(jax.tree_util.tree_leaves(straight.opt.master),
                        jax.tree_util.tree_leaves(restored.opt.master)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(straight.err),
                        jax.tree_util.tree_leaves(restored.err)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """, n_devices=2, timeout=900)
    assert "OK" in out


def test_overlapped_reduce_tree_matches_per_leaf_psum():
    """Packed per-bucket psum is bitwise identical to per-leaf psum (the
    all-reduce is elementwise, so packing cannot change any value)."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core.collectives import overlapped_reduce_tree
        mesh = make_mesh((4,), ("data",))
        k = jax.random.PRNGKey(0)
        xs = {"a": jax.random.normal(k, (4, 37)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (4, 5, 3)),
              "c": jax.random.normal(jax.random.PRNGKey(2), (4, 211))}
        def f(tree):
            packed = overlapped_reduce_tree(
                tree, strategy="psum", data_axes=("data",),
                bucket_bytes=256, world=4, pre_scale=0.5)
            ref = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g * 0.5, ("data",)) / 4, tree)
            return packed, ref
        packed, ref = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=False))(xs)
        for k2 in xs:
            np.testing.assert_array_equal(np.asarray(packed[k2]),
                                          np.asarray(ref[k2]), err_msg=k2)
            assert packed[k2].shape == xs[k2].shape
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def test_gspmd_mode_rejects_overlap():
    from repro.configs import get_config, smoke_variant
    from repro.configs.base import InputShape, TrainConfig
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.sharding import make_rules
    from repro.train.train_step import make_train_step_gspmd
    cfg = smoke_variant(get_config("bert-large"), d_model=64)
    shapes, specs = api.abstract_params(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="overlap_exchange"):
        make_train_step_gspmd(cfg, TrainConfig(overlap_exchange=True),
                              mesh, make_rules(), specs, shapes,
                              InputShape("t", 32, 4, "train"))
