"""Small shared utilities: pytree helpers, rng splitting, logging."""
from __future__ import annotations

import logging
import math
import os
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(levelname)s %(name)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


REPO_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    from ``main()``, never at import.

    ``$JAX_COMPILATION_CACHE_DIR`` is used when set (JAX reads it itself);
    otherwise the fixed ``<repo>/.jax_cache``.  The directory must not move
    between runs: a cache under a fresh temporary path never hits.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves in a pytree."""
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "dtype")
    )


def tree_count(tree: Any) -> int:
    """Total element count (parameter count) of a pytree."""
    return sum(x.size for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "size"))


def tree_cast(tree: Any, dtype) -> Any:
    """Cast every floating leaf of a pytree to ``dtype``."""
    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, tree)


def tree_zeros_like(tree: Any, dtype=None) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


def tree_add(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(tree: Any, s) -> Any:
    return jax.tree_util.tree_map(lambda x: x * s, tree)


def global_norm(tree: Any) -> jax.Array:
    """L2 norm over all leaves (computed in fp32)."""
    sq = sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree_util.tree_leaves(tree)
    )
    return jnp.sqrt(sq)


def all_finite(tree: Any) -> jax.Array:
    """True iff every element of every leaf is finite."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)]
    if not leaves:
        return jnp.asarray(True)
    out = leaves[0]
    for l in leaves[1:]:
        out = jnp.logical_and(out, l)
    return out


def human_bytes(n: float) -> str:
    for unit in ["B", "KiB", "MiB", "GiB", "TiB", "PiB"]:
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_flops(n: float) -> str:
    for unit in ["FLOP", "KFLOP", "MFLOP", "GFLOP", "TFLOP", "PFLOP"]:
        if abs(n) < 1000.0:
            return f"{n:.2f} {unit}"
        n /= 1000.0
    return f"{n:.2f} EFLOP"


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b
