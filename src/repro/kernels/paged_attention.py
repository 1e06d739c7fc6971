"""Paged single-token decode attention Pallas kernel.

The serving KV cache is a global page pool ``(num_pages, page_size, KV, Dh)``
plus a per-slot block table ``(B, max_pages)``; a decode step attends one new
query token per slot over only that slot's live ``kv_len`` tokens.  The kernel
grid is ``(B, KV, max_pages)`` with the page dimension innermost and
sequential: the block table and per-slot lengths ride in as *scalar prefetch*
operands so each page's HBM->VMEM DMA is addressed through
``block_table[b, p]`` -- pages are gathered by the DMA engine, never
materialised contiguously.  Per (slot, kv-head) the kernel keeps running
online-softmax statistics (m, l) and the output accumulator in VMEM scratch
across page steps; pages past ``kv_len`` are skipped entirely (``pl.when``),
and the tail page is masked per token.

int8 pages: per-(page, kv-head) scales are prefetched alongside the pages as
``(1, 1, 1, 1)`` blocks of a ``(KV, P, 1, 1)`` array and the dequantisation
(``int8 * scale``) happens on the VMEM-resident tile right after the load --
fused into the attention math, so HBM only ever carries the 1-byte
representation.

Page-geometry design note (vs MXU/VPU tiling): the KV load tile is
``(page_size, Dh)``.  On TPU the minor dim must span a 128 lane tile --
``Dh`` is 128-padded by the configs -- and the second-minor (sublane) tile is
8 for f32, 16 for bf16 and 32 for int8, so ``page_size`` should be a multiple
of 32 to keep int8 pages tile-aligned (smaller pages waste sublanes, not
correctness).  Larger pages amortise the per-DMA overhead and deepen the MXU
contraction but waste more pool memory per slot (a slot holds on average half
a page of slack) and coarsen the allocator; 32-64 is the sweet spot, and the
CPU/interpret tests use small pages (4-16) since alignment is a TPU-only
performance concern.  The (g, Dh) query tile is small for GQA models -- the
kernel is HBM-bandwidth-bound by the KV stream, which is exactly why halving
cache bytes with int8 pages translates into decode throughput.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, kvl_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, softcap: float, scale: float,
                  n_pages: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    bidx = pl.program_id(0)
    pidx = pl.program_id(2)   # page step (sequential innermost)

    @pl.when(pidx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = kvl_ref[bidx]

    @pl.when(pidx * page_size < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (g, dh)
        k = k_ref[0, 0].astype(jnp.float32)             # (page_size, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:  # dequant fused into the KV load
            k = k * ks_ref[0, 0]                         # (1, 1) scale
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        idx = pidx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(idx < length, s, NEG_INF)         # tail-page mask
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(pidx == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_table, kv_len, *,
                           k_scale=None, v_scale=None, softcap: float = 0.0,
                           interpret: bool = False):
    """q: (B, H, Dh); pages: (P, page_size, KV, Dh); block_table:
    (B, max_pages); kv_len: (B,).  ``k_scale``/``v_scale`` (P, KV) switch on
    the fused int8 dequant.  Returns (B, H, Dh)."""
    b, h, dh = q.shape
    p_total, ps, kvh, _ = k_pages.shape
    mp = block_table.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(dh)
    quantized = k_scale is not None

    qg = q.reshape(b, kvh, g, dh)
    # kv-head axis leading so a page block is a clean (page_size, Dh) tile
    kp = jnp.moveaxis(k_pages, 2, 0)                    # (KV, P, ps, Dh)
    vp = jnp.moveaxis(v_pages, 2, 0)
    bt = jnp.clip(block_table.astype(jnp.int32), 0, p_total - 1)
    kvl = jnp.asarray(kv_len, jnp.int32).reshape((b,))

    def page_map(bi, hi, pi, bt, kvl):
        return (hi, bt[bi, pi], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, dh), lambda bi, hi, pi, bt, kvl: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, ps, dh), page_map),
        pl.BlockSpec((1, 1, ps, dh), page_map),
    ]
    inputs = [qg, kp, vp]
    if quantized:
        # (KV, P, 1, 1): a (1, 1) block of a (KV, P) array is not
        # tile-aligned on TPU; trailing unit dims make it span the array
        in_specs += [pl.BlockSpec((1, 1, 1, 1), lambda bi, hi, pi, bt, kvl:
                                  (hi, bt[bi, pi], 0, 0))] * 2
        inputs += [jnp.swapaxes(sc, 0, 1).astype(jnp.float32)[..., None, None]
                   for sc in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, hi, pi, bt, kvl: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    kernel = partial(_paged_kernel, page_size=ps, softcap=softcap,
                     scale=scale, n_pages=mp, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), q.dtype),
        interpret=interpret,
    )(bt, kvl, *inputs)
    return out.reshape(b, h, dh)
