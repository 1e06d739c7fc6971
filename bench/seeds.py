"""What every family draws its inputs and weights from: ``--seed``."""
from __future__ import annotations

import jax


def seed_words(seed: int) -> list:
    """Non-negative 32-bit words of any whole ``seed``, for numpy's RNG."""
    s = int(seed) % (1 << 64)
    return [s >> 32, s & 0xFFFFFFFF]


def seed_key(seed: int) -> jax.Array:
    hi, lo = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)
