"""Compile the main-path kernels and the BERT-large train step for a TPU v5e.

Nothing runs: each program is lowered and compiled against a *described*
``v5e:2x2`` topology, which is enough for the TPU compiler to refuse what the
chip would refuse (block shapes that are not tile-aligned, kernels that
overflow VMEM, a step that does not fit HBM).  Interpret-mode kernel tests
cannot see any of that.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.kernels import ops
from repro.models import api
from repro.train.train_step import init_train_state, make_train_step_dp

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to a persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b,h,s,dh,causal", [
    (8, 16, 512, 64, False),     # bert-large, phase 2
    (1, 32, 2048, 128, True),    # deepseek-7b prefill
])
def test_flash_attention_fwd_bwd_compiles(one_chip, b, h, s, dh, causal):
    x = _struct((b, h, s, dh), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, causal=causal, impl="pallas")
        return out.astype(jnp.float32).sum()

    compiled = _compile_kernel(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    # forward + the dq and dk/dv backward kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("cache_dtype,page_size", [
    (jnp.bfloat16, 16), (jnp.int8, 32)])
def test_paged_decode_compiles(one_chip, cache_dtype, page_size):
    b, h, dh, n_pages, max_pages = 8, 32, 128, 256, 16
    q = _struct((b, h, dh), jnp.bfloat16, one_chip)
    pages = _struct((n_pages, page_size, h, dh), cache_dtype, one_chip)
    table = _struct((b, max_pages), jnp.int32, one_chip)
    kv_len = _struct((b,), jnp.int32, one_chip)
    if cache_dtype == jnp.int8:
        scale = _struct((n_pages, h), jnp.float32, one_chip)

        def fn(q, kp, vp, bt, kvl, ks, vs):
            return ops.paged_decode_attention(q, kp, vp, bt, kvl, k_scale=ks,
                                              v_scale=vs, impl="pallas")
        _compile_kernel(fn, q, pages, pages, table, kv_len, scale, scale)
    else:
        def fn(q, kp, vp, bt, kvl):
            return ops.paged_decode_attention(q, kp, vp, bt, kvl,
                                              impl="pallas")
        _compile_kernel(fn, q, pages, pages, table, kv_len)


def test_wkv6_compiles(one_chip):
    cfg = get_config("rwkv6-1.6b")
    h, hs = cfg.n_heads, cfg.rwkv_head_size
    x = _struct((1, 2048, h, hs), jnp.float32, one_chip)
    u = _struct((h, hs), jnp.float32, one_chip)
    s0 = _struct((1, h, hs, hs), jnp.float32, one_chip)
    _compile_kernel(lambda r, k, v, w, u, s0: ops.wkv6(r, k, v, w, u, s0,
                                                       impl="pallas"),
                    x, x, x, x, u, s0)


def test_layernorm_compiles(one_chip):
    x = _struct((4096, 1024), jnp.bfloat16, one_chip)
    p = _struct((1024,), jnp.float32, one_chip)
    _compile_kernel(lambda x, s, b: ops.layernorm(x, s, b, impl="pallas"),
                    x, p, p)


def test_bias_gelu_compiles(one_chip):
    x = _struct((4096, 4096), jnp.bfloat16, one_chip)
    b = _struct((4096,), jnp.bfloat16, one_chip)
    _compile_kernel(lambda x, b: ops.bias_gelu(x, b, impl="pallas"), x, b)


def test_lamb_update_compiles(one_chip):
    leaf = _struct((1024, 4096), jnp.float32, one_chip)
    step = _struct((), jnp.int32, one_chip)

    def fn(w, g, m, v, step):
        return ops.lamb_leaf_update(w, g, m, v, lr=1e-3, b1=0.9, b2=0.999,
                                    eps=1e-6, wd=0.01, step=step,
                                    impl="pallas")
    _compile_kernel(fn, leaf, leaf, leaf, leaf, step)


@pytest.mark.parametrize("seq,batch", [(128, 32), (512, 8)])
def test_bert_large_dp_step_fits_one_chip(topo, seq, batch):
    """The full-width BERT-large step chip_smoke.py runs: both phase shapes,
    bf16, LAMB, accumulation 2, psum DP on a one-chip mesh."""
    cfg = get_config("bert-large")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    repl = NamedSharding(mesh, P())
    tcfg = TrainConfig(precision="bf16", accum_steps=2, optimizer="lamb",
                       total_steps=10, warmup_steps=2)
    step, b_struct = make_train_step_dp(
        cfg, tcfg, mesh, InputShape("smoke", seq, batch, "train"))
    param_shapes, _ = api.abstract_params(cfg)
    state = jax.eval_shape(
        lambda p: init_train_state(p, make_policy("bf16"), tcfg),
        param_shapes)
    place = lambda t: jax.tree_util.tree_map(
        lambda a: _struct(a.shape, a.dtype, repl), t)
    compiled = step.lower(place(state), place(b_struct)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
             mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
