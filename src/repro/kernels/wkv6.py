"""WKV6 chunk-parallel Pallas kernel (RWKV-6 time-mix recurrence).

TPU adaptation of the CUDA WKV kernel (DESIGN.md §2): grid = (B*H, n_chunks)
with the chunk dimension sequential; the (hs x hs) recurrent state lives in
VMEM scratch across chunks.  Per chunk of length L the kernel computes the
decay-weighted intra-chunk attention, the cross-chunk state contribution and
the state update -- all exponents are ordered cumulative-decay differences
(<= 0), so the math is fp32-safe without loss-scaling tricks (see
models/rwkv.py for the derivation; identical formulation, VMEM-resident).

VMEM working set per program: a few (L, hs) tiles + the (hs, hs) state,
well under 1 MB at L = hs = 64.  The intra-chunk term walks the chunk's L
key rows in a loop instead of materialising the (L, L, hs) decay tensor.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, ca: int, cb: int):
    """fp32 matmul contracting a's dim ``ca`` with b's dim ``cb``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref,
                 s_scr, k_scr, v_scr, c_scr, *, chunk: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)     # (L, hs)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)     # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)     # (1, hs)
    s = s_scr[...]

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum lowering)
    c = _dot(jnp.where(lj <= li, 1.0, 0.0), w, 1, 0)   # (L, hs)
    c_prev = c - w
    # intra-chunk: o_i += sum_{j<i} (sum_c r_i[c] k_j[c] e^{c_{i-1}[c]-c_j[c]})
    # v_j, one key row j per iteration -- the (L, L, hs) decay tensor of the
    # jnp formulation needs a rank-3 broadcast Mosaic cannot lower
    k_scr[...] = k
    v_scr[...] = v
    c_scr[...] = c
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def intra(jj, o):
        kj = k_scr[pl.ds(jj, 1), :]                    # (1, hs)
        cj = c_scr[pl.ds(jj, 1), :]
        e = jnp.where(rows > jj, jnp.exp(jnp.minimum(c_prev - cj, 0.0)), 0.0)
        a = jnp.sum(r * e * kj, axis=-1, keepdims=True)  # (L, 1)
        return o + a * v_scr[pl.ds(jj, 1), :]

    o = jax.lax.fori_loop(0, chunk, intra, jnp.zeros_like(v))
    # current-token bonus
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)
    o = o + bonus * v
    # cross-chunk
    o = o + _dot(r * jnp.exp(c_prev), s, 1, 0)
    # state update: row i of s decays by exp(c_last[i]), applied as
    # diag(exp(c_last)) @ s so the decay stays a lane vector
    c_last = c[-1:, :]                                  # (1, hs)
    k_eff = k * jnp.exp(c_last - c)
    hs = s.shape[0]
    hi = jax.lax.broadcasted_iota(jnp.int32, (hs, hs), 0)
    hj = jax.lax.broadcasted_iota(jnp.int32, (hs, hs), 1)
    decay = jnp.where(hi == hj, jnp.exp(c_last), 0.0)
    s_new = _dot(decay, s, 1, 0) + _dot(k_eff, v, 0, 0)
    s_scr[...] = s_new
    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _finish():
        sf_ref[0] = s_new.astype(sf_ref.dtype)


def wkv6(r, k, v, logw, u, s0, *, chunk: int = 64,
         interpret: bool = False):
    """r,k,v,logw: (B, S, H, hs); u: (H, hs); s0: (B, H, hs, hs).

    Returns (o (B, S, H, hs) fp32, s_final (B, H, hs, hs) fp32).
    """
    b, s, h, hs = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    def to_bh(x):  # (B,S,H,hs) -> (B*H, S, hs)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hs)

    rf, kf, vf, wf = map(to_bh, (r, k, v, logw))
    # (B*H, 1, hs): a (1, hs) block of a (B*H, hs) array is not tile-aligned
    uf = jnp.broadcast_to(u[None], (b, h, hs)).reshape(b * h, 1, hs)
    s0f = s0.reshape(b * h, hs, hs)

    seq_spec = pl.BlockSpec((1, chunk, hs), lambda bh, j: (bh, j, 0))
    o, sf = pl.pallas_call(
        partial(_wkv6_kernel, chunk=chunk, n_chunks=nc),
        grid=(b * h, nc),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, hs), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, hs, hs), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, hs, hs), lambda bh, j: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, hs), jnp.float32),
            jax.ShapeDtypeStruct((b * h, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)] +
                       [pltpu.VMEM((chunk, hs), jnp.float32)] * 3,
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0f)
    o = o.reshape(b, h, s, hs).transpose(0, 2, 1, 3)
    return o, sf.reshape(b, h, hs, hs)
