"""Compressed gradient exchange (fp16 / int8 wire + error feedback).

Single-process tests cover the quantiser contract and the analytic byte
accounting behind BENCH_train.json; subprocess tests (forced host devices)
cover the compressed all-reduce vs psum, error-feedback/non-finite
semantics, and exact resume with the TrainState.err buffer checkpointed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro.configs.base import TrainConfig
from repro.core.collectives import (GRAD_COMPRESSIONS, dequantize_int8,
                                    exchange_bytes_per_step, quantize_int8)


# ---------------------------------------------------------------------------
# Quantiser contract
# ---------------------------------------------------------------------------

def test_quantize_int8_bounds_and_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (513,)) * 3.0
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert int(jnp.max(jnp.abs(q))) <= 127
    np.testing.assert_allclose(
        float(scale), float(jnp.max(jnp.abs(x))) / 127.0, rtol=1e-6)
    # symmetric rounding: per-element error bounded by half a quantum
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_quantize_int8_zero_input_is_safe():
    q, scale = quantize_int8(jnp.zeros((16,)))
    assert float(scale) > 0  # absmax floor prevents divide-by-zero
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, scale)), 0.0)


def test_grad_compression_config_values():
    assert GRAD_COMPRESSIONS == ("none", "fp16", "int8")
    assert TrainConfig().grad_compression == "none"


# ---------------------------------------------------------------------------
# Analytic wire-byte accounting (the acceptance-criterion numbers)
# ---------------------------------------------------------------------------

def test_exchange_bytes_compression_ratios():
    n_params = 1_000_000
    kw = dict(strategy="psum", world=4, bucket_bytes=1 << 16)
    base = exchange_bytes_per_step(n_params, compression="none", **kw)
    fp16 = exchange_bytes_per_step(n_params, compression="fp16", **kw)
    int8 = exchange_bytes_per_step(n_params, compression="int8", **kw)
    np.testing.assert_allclose(base / fp16, 2.0, rtol=1e-6)
    assert base / int8 >= 3.0  # ISSUE acceptance: >= 3x fewer wire bytes
    assert base / int8 < 4.0   # ... the per-bucket fp32 scales cost something
    # single worker exchanges nothing
    assert exchange_bytes_per_step(n_params, strategy="ring",
                                   compression="int8", world=1) == 0.0


def test_exchange_bytes_hierarchical_volume_and_ratio():
    """Hierarchical conserves total per-worker volume -- its 2(n-1)/n words
    split as (f-1)/f on the fast link + (p-1)/(pf) on the slow one sum to
    the flat formula algebraically; the win is WHERE bytes go, not how
    many.  The int8 ratio must survive the hierarchical/pod layout too."""
    n_params = 1_000_000
    for comp in ("none", "fp16", "int8"):
        hier = exchange_bytes_per_step(n_params, strategy="hierarchical",
                                       compression=comp, world=8, pod=2,
                                       bucket_bytes=1 << 16)
        flat = exchange_bytes_per_step(n_params, strategy="psum",
                                       compression=comp, world=8,
                                       bucket_bytes=1 << 16)
        np.testing.assert_allclose(hier, flat, rtol=1e-9, err_msg=comp)
    base = exchange_bytes_per_step(n_params, strategy="hierarchical",
                                   compression="none", world=8, pod=2)
    int8 = exchange_bytes_per_step(n_params, strategy="hierarchical",
                                   compression="int8", world=8, pod=2,
                                   bucket_bytes=1 << 16)
    assert base / int8 >= 3.0


def test_gspmd_mode_rejects_compression():
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_mesh
    from repro.configs.base import InputShape
    from repro.models import api
    from repro.sharding import make_rules
    from repro.train.train_step import make_train_step_gspmd
    cfg = smoke_variant(get_config("bert-large"), d_model=64)
    shapes, specs = api.abstract_params(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="grad_compression"):
        make_train_step_gspmd(cfg, TrainConfig(grad_compression="fp16"),
                              mesh, make_rules(), specs, shapes,
                              InputShape("t", 32, 4, "train"))


# ---------------------------------------------------------------------------
# Multi-device: compressed exchange vs psum, EF + non-finite semantics
# ---------------------------------------------------------------------------

def test_compressed_reduce_matches_psum_and_feeds_back_error():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core.collectives import (compressed_reduce_gradients,
                                            quantize_int8, dequantize_int8)
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 37)) * 2.0
        ref = np.tile(np.asarray(x).sum(0)[None], (4, 1))
        for mode, tol in [("fp16", 1e-3), ("int8", 5e-2)]:
            def f(g):
                tree = {"w": g}
                err = {"w": jnp.zeros_like(g, jnp.float32)}
                red, new_err, fin = compressed_reduce_gradients(
                    tree, err, strategy="psum", mode=mode,
                    data_axes=("data",), bucket_bytes=64)
                return red["w"], new_err["w"], fin
            red, new_err, fin = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("data", None),
                out_specs=(P("data", None), P("data", None), P()),
                check_vma=False))(x)
            assert bool(np.all(np.asarray(fin))), mode
            np.testing.assert_allclose(np.asarray(red), ref, rtol=tol,
                                       atol=tol * np.abs(ref).max(),
                                       err_msg=mode)
            # residual really is the local quantisation error: adding it
            # back to the compressed value recovers the input exactly
            if mode == "fp16":
                rec = np.asarray(x).astype(np.float16).astype(np.float32)
                np.testing.assert_allclose(np.asarray(new_err),
                                           np.asarray(x) - rec, atol=1e-7)
            assert float(np.abs(np.asarray(new_err)).max()) > 0, mode
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def test_compressed_reduce_nonfinite_worker_holds_residual():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core.collectives import compressed_reduce_gradients
        mesh = make_mesh((4,), ("data",))
        x = jnp.ones((4, 16))
        x = x.at[2, 3].set(jnp.nan)  # worker 2 overflows
        err0 = jnp.full((4, 16), 0.25)
        def f(g, e):
            red, new_err, fin = compressed_reduce_gradients(
                {"w": g}, {"w": e}, strategy="psum", mode="int8",
                data_axes=("data",), bucket_bytes=1 << 16)
            return red["w"], new_err["w"], fin
        red, new_err, fin = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None), P()),
            check_vma=False))(x, err0)
        # one bad worker poisons nobody: flag is globally False ...
        assert not bool(np.asarray(fin))
        # ... the exchange still produces finite numbers (zeros + residual)
        assert np.all(np.isfinite(np.asarray(red)))
        # ... and the feedback buffer is held, not advanced
        np.testing.assert_array_equal(np.asarray(new_err),
                                      np.asarray(err0))
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def test_compressed_exact_resume_with_err_buffer():
    """PR 7 manifest carries TrainState.err: 2 steps + checkpoint + restore
    + 2 steps is bit-identical to 4 straight steps under int8 compression."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_config, smoke_variant
        from repro.configs.base import TrainConfig, InputShape
        from repro.core.amp import make_policy
        from repro.models import api
        from repro.train.checkpoint import (restore_checkpoint,
                                            save_checkpoint)
        from repro.train.train_step import (init_train_state,
                                            make_train_step_dp)
        from repro.launch.mesh import make_mesh
        cfg = smoke_variant(get_config("bert-large"), d_model=64)
        shape = InputShape("t", 32, 8, "train")
        tcfg = TrainConfig(precision="f32", accum_steps=1, total_steps=10,
                           warmup_steps=1, collective_strategy="psum",
                           grad_compression="int8", bucket_bytes=1 << 16)
        mesh = make_mesh((2,), ("data",))
        step, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
        params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
        batches = [api.make_synth_batch(jax.random.PRNGKey(i), cfg, shape)
                   for i in range(4)]

        state = init_train_state(params, make_policy("f32"), tcfg, world=2)
        assert state.err is not None  # compression allocates the buffer
        for b in batches:
            state, _ = step(state, b)
        straight = state

        state = init_train_state(params, make_policy("f32"), tcfg, world=2)
        for b in batches[:2]:
            state, _ = step(state, b)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 2, state)
        restored, at = restore_checkpoint(d, jax.tree_util.tree_map(
            jnp.zeros_like, state))
        assert at == 2
        # the residual buffer must round-trip exactly ...
        for a, b in zip(jax.tree_util.tree_leaves(restored.err),
                        jax.tree_util.tree_leaves(state.err)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for b in batches[2:]:
            restored, _ = step(restored, b)
        # ... so resumed and straight-through runs match bit for bit
        for a, b in zip(jax.tree_util.tree_leaves(straight.opt.master),
                        jax.tree_util.tree_leaves(restored.opt.master)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(straight.err),
                        jax.tree_util.tree_leaves(restored.err)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """, n_devices=2, timeout=900)
    assert "OK" in out
