"""LAMB optimizer (You et al., paper ref [24]) -- pure JAX.

The paper uses LAMB for large-batch BERT pretraining and fuses its update
via APEX (§4.3).  Here: fp32 master weights + (m, v) moments, layer-wise
trust ratio ||w|| / ||update||, decoupled weight decay.  The elementwise
part of the update is additionally available as a fused Pallas kernel in
kernels/lamb_update.py (ops.lamb_update_fused); the trust-ratio norms are
reductions and stay in XLA either way.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class LambState(NamedTuple):
    step: jax.Array     # i32
    master: Any         # fp32 master params (paper §4.2: FP32 replica)
    m: Any              # fp32 first moment
    v: Any              # fp32 second moment


def lamb_init(params) -> LambState:
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    zeros = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return LambState(jnp.int32(0), f32(params), zeros(params), zeros(params))


def _lamb_leaf(w, g, m, v, *, lr, b1, b2, eps, wd, step, fused: bool):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    update = mhat / (jnp.sqrt(vhat) + eps) + wd * w
    wnorm = jnp.linalg.norm(w.reshape(-1))
    unorm = jnp.linalg.norm(update.reshape(-1))
    trust = jnp.where(wnorm > 0, jnp.where(unorm > 0, wnorm / unorm, 1.0), 1.0)
    return w - lr * trust * update, m, v


def lamb_update(grads, state: LambState, *, lr, b1: float = 0.9,
                b2: float = 0.999, eps: float = 1e-6, wd: float = 0.01,
                skip_update: Optional[jax.Array] = None,
                use_fused_kernel: bool = False):
    """One LAMB step.  grads fp32.  Returns (new_state, compute_params_fn).

    ``skip_update``: bool scalar -- when False (e.g. non-finite fp16 grads,
    paper §4.2 dynamic loss scaling), state is returned unchanged except
    the loss-scale bookkeeping handled by the caller.  Its ops carry the
    named scope ``lamb``.
    """
    with jax.named_scope("lamb"):
        step = state.step + 1
        lr = jnp.asarray(lr, jnp.float32)

        if use_fused_kernel:
            from repro.kernels import ops as kops
            leaf_fn = lambda w, g, m, v: kops.lamb_leaf_update(
                w, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
                step=step)
        else:
            leaf_fn = lambda w, g, m, v: _lamb_leaf(
                w, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd, step=step,
                fused=False)

        new = jax.tree_util.tree_map(leaf_fn, state.master, grads, state.m,
                                     state.v)
        outer = jax.tree_util.tree_structure(state.master)
        inner = jax.tree_util.tree_structure((0, 0, 0))
        new_w, new_m, new_v = jax.tree_util.tree_transpose(outer, inner, new)

        if skip_update is not None:
            keep = lambda new_t, old_t: jax.tree_util.tree_map(
                lambda n, o: jnp.where(skip_update, o, n), new_t, old_t)
            new_w = keep(new_w, state.master)
            new_m = keep(new_m, state.m)
            new_v = keep(new_v, state.v)
            step = jnp.where(skip_update, state.step, step)

        return LambState(step, new_w, new_m, new_v)
