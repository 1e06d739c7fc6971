"""Fused bias + tanh-GELU Pallas kernel (paper §4.3's 7-kernels->1 example).

On GPU the win is kernel-launch overhead + locality; on TPU the chain is a
single VMEM-resident VPU pass: one HBM read of x, one write of y, with the
bias broadcast from VMEM.  Tiles are (block_rows, d) with d padded to the
128-lane register width by the caller; block_rows shrinks as d grows so the
double-buffered tiles and the fp32 intermediates stay inside the default
scoped VMEM (a 256-row tile at d = 4096 does not).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)
# fp32 elements per tile: 512 KiB, so in/out double buffers plus a few fp32
# temporaries of one tile fit the 16 MiB scoped VMEM at any width
TILE_ELEMS = 128 * 1024


def _bias_gelu_kernel(x_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y = x + b[None, :]
    inner = SQRT_2_OVER_PI * (y + 0.044715 * y * y * y)
    o_ref[...] = (0.5 * y * (1.0 + jnp.tanh(inner))).astype(o_ref.dtype)


def bias_gelu(x: jax.Array, b: jax.Array, *, interpret: bool = False
              ) -> jax.Array:
    """x: (..., d); b: (d,).  Leading dims are flattened into rows."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    block_rows = min(max(32, TILE_ELEMS // d // 32 * 32), rows)
    # pad rows to a multiple of the block
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n_blocks = x2.shape[0] // block_rows

    out = pl.pallas_call(
        _bias_gelu_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, b)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
