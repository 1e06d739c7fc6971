"""FlashAttention forward Pallas kernel (TPU adaptation of the paper's
kernel-fusion layer applied to the attention hot-spot).

Design (DESIGN.md §2): never materialise the (S, S) score matrix in HBM.
Grid = (B*H, nq, nk) with the kv dimension innermost and *sequential*
("arbitrary" semantics): each (bh, i) q tile keeps running online-softmax
statistics (m, l) and the output accumulator in VMEM scratch across the nk
steps.  Block shapes are MXU-aligned: (block_q, Dh) x (block_k, Dh) tiles.

Per-row statistics (m, l, the logsumexp and the backward's delta) are
(block_q, 1) columns.  In HBM they are (B*H, S, 1) arrays: a (1, block_q)
row block of a (B*H, S) array is not tile-aligned on TPU (its sublane dim
is 1), while a (1, block_q, 1) block spans the full minor dim.

The backward pass is the FlashAttention-2 pair of kernels below.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(i, j, block_q, block_k, causal, window):
    qi = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        m &= ki <= qi
    if window:
        m &= ki > qi - window
    return m


def _block_live(i, j, block_q, block_k, causal, window):
    """Whether the (i, j) tile intersects the mask at all (skip otherwise)."""
    live = True
    if causal:
        live = (j * block_k) <= (i * block_q + block_q - 1)
    if window:
        # newest k in tile must be > oldest q in tile - window
        live = jnp.logical_and(
            live, (j + 1) * block_k - 1 > i * block_q - window)
    return live


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, block_q: int, block_k: int, causal: bool,
                  window: int, softcap: float, scale: float, n_k: int):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(i, j, block_q, block_k, causal, window))
    def _step():
        q = q_ref[0].astype(jnp.float32)       # (block_q, dh)
        k = k_ref[0].astype(jnp.float32)       # (block_k, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        if causal or window:
            s = jnp.where(_mask(i, j, block_q, block_k, causal, window),
                          s, NEG_INF)

        m_prev = m_ref[...]                    # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False,
                    return_lse: bool = False):
    """q,k,v: (B, H, S, Dh) with equal head counts (wrapper expands GQA).

    Supports sliding-window masking (gemma2 local layers) and tanh logit
    soft-capping.  Returns (B, H, S, Dh) [, lse (B, H, S)].
    """
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    nq, nk = sq // block_q, skv // block_k
    scale = 1.0 / math.sqrt(dh)

    qf = q.reshape(b * h, sq, dh)
    kf = k.reshape(b * h, skv, dh)
    vf = v.reshape(b * h, skv, dh)

    kernel = partial(_flash_kernel, block_q=block_q, block_k=block_k,
                     causal=causal, window=window, softcap=softcap,
                     scale=scale, n_k=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, sq, dh)
    if return_lse:
        return out, lse.reshape(b, h, sq)
    return out


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2): dq accumulated over kv blocks;
# dk/dv accumulated over q blocks in a second pass.
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, block_q, block_k, causal,
                         window, softcap, scale, n_k):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(i, j, block_q, block_k, causal, window))
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * scale
        capped = softcap * jnp.tanh(raw / softcap) if softcap else raw
        mask = _mask(i, j, block_q, block_k, causal, window)
        capped = jnp.where(mask, capped, NEG_INF)
        p = jnp.exp(capped - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        if softcap:
            ds = ds * (1.0 - jnp.square(jnp.where(mask, capped / softcap,
                                                  0.0)))
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q,
                          block_k, causal, window, softcap, scale, n_q):
    j = pl.program_id(1)   # kv block (outer)
    i = pl.program_id(2)   # q block (sequential innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(i, j, block_q, block_k, causal, window))
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * scale
        capped = softcap * jnp.tanh(raw / softcap) if softcap else raw
        mask = _mask(i, j, block_q, block_k, causal, window)
        capped = jnp.where(mask, capped, NEG_INF)
        p = jnp.exp(capped - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        if softcap:
            ds = ds * (1.0 - jnp.square(jnp.where(mask, capped / softcap,
                                                  0.0)))
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal=True, window=0,
                        softcap=0.0, block_q=256, block_k=256,
                        interpret=False):
    """FlashAttention-2 backward.  All (B, H, S, Dh); lse (B, H, S).

    Returns (dq, dk, dv)."""
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    scale = 1.0 / math.sqrt(dh)

    qf = q.reshape(b * h, sq, dh)
    kf = k.reshape(b * h, skv, dh)
    vf = v.reshape(b * h, skv, dh)
    dof = dout.reshape(b * h, sq, dh)
    lsef = lse.reshape(b * h, sq, 1)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, sq, 1)

    q_spec = pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0))
    r_spec = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))

    dq = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                causal=causal, window=window, softcap=softcap, scale=scale,
                n_k=nk),
        grid=(b * h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    # second pass: kv blocks outer, q blocks inner
    q_spec2 = pl.BlockSpec((1, block_q, dh), lambda bh, j, i: (bh, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, dh), lambda bh, j, i: (bh, j, 0))
    r_spec2 = pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0))
    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                causal=causal, window=window, softcap=softcap, scale=scale,
                n_q=nq),
        grid=(b * h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b * h, skv, dh), k.dtype),
                   jax.ShapeDtypeStruct((b * h, skv, dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)
    return (dq.reshape(b, h, sq, dh), dk.reshape(b, h, skv, dh),
            dv.reshape(b, h, skv, dh))
