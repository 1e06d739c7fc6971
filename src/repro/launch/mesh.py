"""Production mesh construction.

Single pod : (data=16, model=16)            -- 256 chips (TPU v5e pod)
Multi pod  : (pod=2, data=16, model=16)     -- 512 chips over DCN

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before building the mesh).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes: jax's default is Explicit axes,
    whereas the sharding rules here place arrays through GSPMD.
    ``devices`` defaults to every device."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=None) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (1, n), ("data", "model")
    return make_mesh(shape, axes)


HW = {
    # TPU v5e per-chip constants used by the roofline (DESIGN.md §5)
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
    "dcn_bw": 6.25e9,              # B/s per host (~50 Gb/s), cross-pod
    "chips_per_pod": 256,
}
