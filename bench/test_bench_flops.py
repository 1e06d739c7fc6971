"""bench/flops.py and bench/peaks.json, checked on the CPU.

The model-FLOPs function is compared with the matmuls of the program's own
BERT loss and gradient at a reduced width: counted exactly from the traced
program, and as the total that XLA's ``cost_analysis()`` reports for the
compiled CPU program, of which matmuls are nearly all.
"""
import dataclasses
import math

import jax
import pytest

from bench import flops
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.amp import make_policy
from repro.models import api

# one layer: XLA's cost analysis counts a loop body once, whatever its trip
# count, and the program scans its layers
SMALL = dict(n_layers=1, d_model=256, n_heads=4, head_dim=64, d_ff=1024,
             vocab_size=2048)
SEQ, PRED, BATCH = 128, 20, 8


def _dot_flops(jaxpr) -> float:
    """2 * M * N * K summed over every dot_general, through scans and
    nested programs."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[i] for i in lc)
            total += 2.0 * k * math.prod(eqn.outvars[0].aval.shape)
            continue
        mult = eqn.params.get("length", 1) if eqn.primitive.name == "scan" \
            else 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += mult * _dot_flops(inner)
    return total


def _grad_program(n_layers):
    cfg = dataclasses.replace(get_config("bert-large"), n_kv_heads=4,
                              **dict(SMALL, n_layers=n_layers))
    params = api.init_params(jax.random.PRNGKey(0), cfg)[0]
    batch = api.make_synth_batch(jax.random.PRNGKey(1), cfg,
                                 InputShape("t", SEQ, BATCH, "train"))
    loss_fn = api.make_loss_fn(cfg, make_policy("f32"), remat=False)
    fn = jax.value_and_grad(lambda p, b: loss_fn(p, b)[0])
    return fn, params, batch


def _model_flops(n_layers=1):
    cfg = dict(SMALL, n_layers=n_layers)
    return flops.train_flops_per_token(cfg, SEQ, PRED) * SEQ * BATCH


def test_matches_the_programs_matmuls():
    fn, params, batch = _grad_program(3)
    assert batch["mlm_positions"].shape[1] == PRED
    counted = _dot_flops(jax.make_jaxpr(fn)(params, batch).jaxpr)
    assert counted == pytest.approx(_model_flops(3), rel=1e-3)


def test_is_most_of_what_xla_counts():
    fn, params, batch = _grad_program(1)
    cost = jax.jit(fn).lower(params, batch).compile().cost_analysis()
    xla = float(cost["flops"])
    # XLA also counts softmax, GeLU, LayerNorm and the loss: a few percent
    assert _model_flops() <= xla <= 1.08 * _model_flops()


def test_bert_large_phase1_per_token():
    cfg = {"n_layers": 24, "d_model": 1024, "n_heads": 16, "head_dim": 64,
           "d_ff": 4096, "vocab_size": 30522}
    assert flops.train_flops_per_token(cfg, 128, 20) == \
        pytest.approx(1.88e9, rel=5e-3)


def test_peaks_are_keyed_by_device_kind():
    v5e = flops.chip_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        flops.chip_peaks("cpu")
