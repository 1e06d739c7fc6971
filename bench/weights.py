"""BERT weights made from a seed, in the parameter layout the program takes.

The benchmark makes the weights itself, so that the program under test and
the plain reference start from the same numbers and neither takes them from
the other.  Every matrix is BERT's truncated normal (two standard
deviations, ``initializer_range``); LayerNorm scales are 1 and biases 0.
Layer parameters are stacked along a leading layer axis, as the program
scans its blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.traffic import seed_words


def seed_key(seed: int) -> jax.Array:
    hi, lo = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def init_weights(key: jax.Array, cfg: dict) -> dict:
    """fp32 weights; call under ``jax.jit`` to make them on the device."""
    n, d, ff = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    v = cfg["vocab_size"]
    h, dh = cfg["n_heads"], cfg["head_dim"]
    std = cfg["initializer_range"]
    count = iter(range(1000))

    def normal(*shape):
        k = jax.random.fold_in(key, next(count))
        return std * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                 jnp.float32)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), jnp.float32),
                "bias": jnp.zeros(lead + (d,), jnp.float32)}

    return {
        "embed": {"tok": normal(v, d), "pos": normal(cfg["max_position"], d),
                  "type": normal(cfg["type_vocab_size"], d)},
        "embed_norm": norm(),
        "blocks": {
            "attn": {"wq": normal(n, d, h, dh), "wk": normal(n, d, h, dh),
                     "wv": normal(n, d, h, dh), "wo": normal(n, h, dh, d)},
            "attn_norm": norm(n),
            "mlp": {"wi": normal(n, d, ff), "bi": jnp.zeros((n, ff)),
                    "wo": normal(n, ff, d), "bo": jnp.zeros((n, d))},
            "mlp_norm": norm(n),
        },
        "mlm_transform": {"w": normal(d, d), "b": jnp.zeros((d,))},
        "mlm_norm": norm(),
        "mlm_bias": jnp.zeros((v,)),
        "pooler": {"w": normal(d, d), "b": jnp.zeros((d,))},
        "nsp": {"w": normal(d, 2), "b": jnp.zeros((2,))},
    }
