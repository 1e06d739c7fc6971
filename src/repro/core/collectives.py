"""Gradient-exchange collectives (paper §3.2, §4.4).

Three strategies, selectable per training config:

  * ``psum``            -- XLA's native all-reduce (what NCCL's auto-detected
                           ring is to PyTorch; the production default).
  * ``ring``            -- a faithful reimplementation of NCCL's ring
                           all-reduce [31] out of ``lax.ppermute``:
                           N-1 reduce-scatter hops + N-1 all-gather hops.
                           Validated equal to ``psum``; its collective-permute
                           ops are visible in the dry-run HLO, making the
                           paper's mechanism inspectable on TPU.
  * ``hierarchical``    -- the paper's slow-link optimisation (PCIe vs
                           10Gb/s Ethernet) mapped to ICI vs DCN:
                           reduce-scatter inside the pod, all-reduce the
                           1/N shard across pods, all-gather inside the pod.

Plus ``bucketed_psum``: the paper's comm/compute *overlap* (§4.4, Fig 2).
PyTorch DDP overlaps by all-reducing gradient buckets as backward produces
them; under XLA the analogous lever is issuing one collective per bucket
(instead of one giant fused all-reduce) so the latency-hiding scheduler can
pipeline collectives with the remaining backward compute.

Plus ``overlapped_reduce_tree``: the packed form of that idea, used by the
``TrainConfig.overlap_exchange`` drain schedule (see core/grad_accum.py for
the bucket lifecycle).  Each ~``bucket_bytes`` bucket is exchanged as ONE
concatenated flat buffer issued inside the last micro-batch's flat backward
region: elementwise identical to per-leaf psum (bit-exact losses), free for
XLA to overlap with the remaining backward, and O(n_buckets) collective
dispatches instead of O(n_leaves).

Compressed gradient exchange (``TrainConfig.grad_compression``, paper §4.4's
fp16 wire + "How to Train BERT with an Academic Budget" / 1-bit-Adam-style
error feedback):

  * ``fp16``  -- every leaf is cast to fp16 *before* the reduce, so whichever
    wire schedule the strategy picks (psum / ppermute ring / hierarchical /
    bucketed) moves 2-byte words: a straight 2x byte cut that composes with
    all four strategies verbatim.
  * ``int8``  -- gradients are packed into ~``bucket_bytes`` buckets (the
    same ``bucket_leaves`` grouping the overlap path uses) and each bucket is
    symmetrically quantised with ONE fp32 scale (absmax/127 -- mirroring the
    per-page scales of the int8 KV cache).  Int8 partial sums overflow and
    per-hop requantisation compounds error, so the int8 wire schedule is the
    compressed reduce-scatter + all-gather decomposition (DeepSpeed's
    compressed all-reduce; the same 2(n-1)/n volume a ring moves):
    ``all_to_all`` ships each worker's n-th chunk shards as int8, shards are
    dequantised and summed locally, requantised with a fresh per-shard scale,
    and ``all_gather``-ed back as int8 -- ~4x fewer wire bytes than fp32 for
    any world size (see ``exchange_bytes_per_step``).  The strategy knob
    still controls bucket granularity (``bucketed``) and is kept orthogonal
    in configs/benchmarks.
  * **Error feedback**: quantisation is lossy, so the residual
    ``(g + e) - dequantise(quantise(g + e))`` is carried in
    ``TrainState.err`` and added back into the next step's gradients before
    compression -- the compression error becomes delayed, not dropped, and
    the averaged trajectory tracks the uncompressed one (1-bit Adam's
    argument).  The residual is purely local -- each worker's own error --
    so ``TrainState.err`` stacks it along a leading world dim sharded over
    the DP axes (checkpoints carry every worker's buffer; exact-resume is
    bit-identical); the int8 second-stage requantisation error is NOT fed back
    (it would need a per-shard buffer) and is bounded by absmax/254 per
    element per step.  Non-finite local gradients (AMP overflow) are zeroed
    before quantisation and the residual is held, so a skipped step can
    never poison the feedback buffer.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.utils import all_finite


# ---------------------------------------------------------------------------
# Ring all-reduce from ppermute (NCCL's algorithm, paper ref [31]).
# ---------------------------------------------------------------------------

def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce over ``axis_name`` as a reduce-scatter + all-gather ring.

    Must be called inside shard_map/pmap with ``axis_name`` bound.
    The array's leading dim is chunked N ways (padded if needed).
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)  # chunk c lives on everyone; ring reduces it

    perm = [(i, (i + 1) % n) for i in range(n)]

    # Reduce-scatter phase.  At hop k device d sends its running partial sum
    # (initially its own copy of chunk d) and accumulates the received
    # partial into chunk (d-k-1) mod n.  After n-1 hops device d holds the
    # FULL sum of chunk (d+1) mod n.
    def rs_step(k, send):
        recv = jax.lax.ppermute(send, axis_name, perm)
        return jnp.take(chunks, jnp.mod(idx - k - 1, n), axis=0) + recv

    owned = jax.lax.fori_loop(0, n - 1, rs_step, jnp.take(chunks, idx, axis=0))

    # All-gather phase: circulate the owned (fully-reduced) chunk.  At hop k
    # device d receives the full sum of chunk (d-k) mod n.
    out_chunks = jnp.zeros_like(chunks)
    out_chunks = jax.lax.dynamic_update_index_in_dim(
        out_chunks, owned, jnp.mod(idx + 1, n), 0)

    def ag_step(k, carry):
        acc, send = carry
        recv = jax.lax.ppermute(send, axis_name, perm)
        acc = jax.lax.dynamic_update_index_in_dim(
            acc, recv, jnp.mod(idx - k, n), 0)
        return acc, recv

    out_chunks, _ = jax.lax.fori_loop(0, n - 1, ag_step, (out_chunks, owned))

    out = out_chunks.reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(orig_shape)


def ring_all_reduce_tree(tree: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(lambda x: ring_all_reduce(x, axis_name), tree)


# ---------------------------------------------------------------------------
# Hierarchical all-reduce (paper's PCIe-vs-network schedule -> ICI vs DCN).
# ---------------------------------------------------------------------------

def hierarchical_psum(x: jax.Array, fast_axis, slow_axis) -> jax.Array:
    """reduce-scatter(fast) -> psum(slow) -> all-gather(fast).

    The slow (cross-pod DCN) link carries only 1/len(fast_axis) of the
    gradient bytes -- the paper's core multi-node insight.  Falls back to a
    plain two-axis psum when the tensor cannot be evenly scattered.
    """
    fast = (fast_axis,) if isinstance(fast_axis, str) else tuple(fast_axis)
    nf = 1
    for a in fast:
        nf *= jax.lax.axis_size(a)
    flat = x.reshape(-1)
    if flat.size % nf != 0:
        # single fused psum, not psum(psum(fast), slow): the nested form
        # sums in a different order and drifts from the psum strategy in
        # the last float bit (scalar losses land here, size 1 % nf != 0)
        return jax.lax.psum(x, tuple(fast) + (slow_axis,))
    shard = jax.lax.psum_scatter(
        flat.reshape(nf, -1), fast, scatter_dimension=0, tiled=False)
    shard = jax.lax.psum(shard, slow_axis)
    out = jax.lax.all_gather(shard, fast, axis=0, tiled=False)
    return out.reshape(nf, -1).reshape(x.shape)


def hierarchical_psum_tree(tree: Any, fast_axis, slow_axis) -> Any:
    return jax.tree_util.tree_map(
        lambda x: hierarchical_psum(x, fast_axis, slow_axis), tree)


# ---------------------------------------------------------------------------
# Bucketed all-reduce for comm/compute overlap (paper §4.4 Fig 2).
# ---------------------------------------------------------------------------

def bucket_leaves(tree: Any, bucket_bytes: int = 25 * 2 ** 20) -> list:
    """Group pytree leaves into buckets of ~bucket_bytes (DDP-style)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets, cur, cur_bytes = [], [], 0
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize if hasattr(leaf, "dtype") else 0
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_psum_tree(tree: Any, axis_names, *,
                       bucket_bytes: int = 25 * 2 ** 20) -> Any:
    """One psum per ~25MB bucket instead of one fused all-reduce.

    Leaves XLA's latency-hiding scheduler free to overlap early buckets'
    collectives with later buckets' (still-running) backward compute --
    the paper's Fig 2 timeline, compiler-scheduled.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = list(leaves)
    for bucket in bucket_leaves(tree, bucket_bytes):
        reduced = jax.lax.psum(tuple(leaves[i] for i in bucket), axis_names)
        for j, i in enumerate(bucket):
            out[i] = reduced[j]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Strategy dispatch used by the train step.
# ---------------------------------------------------------------------------

def overlapped_reduce_tree(tree: Any, *, strategy: str,
                           data_axes: Sequence[str],
                           pod_axis: Optional[str] = None,
                           bucket_bytes: int = 25 * 2 ** 20,
                           world: int = 1,
                           pre_scale: Optional[float] = None) -> Any:
    """Packed per-bucket exchange for the overlapped drain schedule.

    Each ``bucket_leaves`` bucket is concatenated into ONE flat buffer,
    optionally pre-scaled (the 1/accum_steps mean, folded in here so it
    runs on ~n_buckets buffers instead of n_leaves), reduced with the
    selected wire strategy, divided by ``world`` (the psum -> mean
    contract of the serial ``reduce_fn``), and split back.

    Two properties the drain schedule rides on:

    * **bit-exact vs per-leaf psum**: an all-reduce is elementwise and
      layout-independent, so psum of a concatenated bucket produces the
      exact bits of per-leaf psums; the pre/post scalings are elementwise
      in the same order the serial path applies them.  (The ring/
      hierarchical wire forms re-chunk the flat buffer, which can rotate
      the per-element reduction order -- numerically equivalent, and
      observed bit-equal on the CI harness, but only ``psum``/``bucketed``
      carry the by-construction guarantee.)
    * **schedulable**: each bucket's collective depends only on its own
      leaves, so inside the drain region XLA may issue it while the
      remaining backward compute runs; and the packed form costs
      O(n_buckets) collective dispatches instead of O(n_leaves) -- on the
      forced-host-device CI mesh, where per-op rendezvous dominates, this
      is the measured step-time win.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    axes = tuple(data_axes) + ((pod_axis,) if pod_axis else ())
    out = [None] * len(leaves)
    for bucket in bucket_leaves(tree, bucket_bytes):
        flat = leaves[bucket[0]].reshape(-1) if len(bucket) == 1 else \
            jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        if pre_scale is not None:
            flat = flat * pre_scale
        if strategy == "ring":
            name = axes[0] if len(axes) == 1 else axes
            red = ring_all_reduce(flat, name)
        elif strategy == "hierarchical":
            assert pod_axis is not None, "hierarchical needs a pod axis"
            fast = tuple(a for a in axes if a != pod_axis)
            red = hierarchical_psum(flat, fast, pod_axis)
        else:  # psum and bucketed share the packed form
            red = jax.lax.psum(flat, axes)
        if world > 1:
            red = red / world
        off = 0
        for i in bucket:
            sz = leaves[i].size
            out[i] = red[off:off + sz].reshape(leaves[i].shape)
            off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def reduce_gradients(grads: Any, *, strategy: str, data_axes: Sequence[str],
                     pod_axis: Optional[str] = None,
                     bucket_bytes: int = 25 * 2 ** 20) -> Any:
    """All-reduce ``grads`` over the data-parallel axes inside shard_map."""
    data_axes = tuple(data_axes)
    if strategy == "psum":
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, data_axes + ((pod_axis,) if pod_axis else ())),
            grads)
    if strategy == "bucketed":
        axes = data_axes + ((pod_axis,) if pod_axis else ())
        return bucketed_psum_tree(grads, axes, bucket_bytes=bucket_bytes)
    if strategy == "ring":
        axes = data_axes + ((pod_axis,) if pod_axis else ())
        name = axes[0] if len(axes) == 1 else axes
        return ring_all_reduce_tree(grads, name)
    if strategy == "hierarchical":
        assert pod_axis is not None, "hierarchical needs a pod axis"
        return hierarchical_psum_tree(grads, data_axes, pod_axis)
    raise ValueError(f"unknown collective strategy {strategy!r}")


# ---------------------------------------------------------------------------
# Compressed gradient exchange (fp16 / int8 wire) with error feedback.
# ---------------------------------------------------------------------------

GRAD_COMPRESSIONS = ("none", "fp16", "int8")


def quantize_int8(flat: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-bucket int8: one fp32 scale = absmax/127 (KV-page style)."""
    amax = jnp.max(jnp.abs(flat))
    scale = (jnp.maximum(amax, 1e-12) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _group_size(axes) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def int8_two_stage_all_reduce(q: jax.Array, scale: jax.Array,
                              axes) -> jax.Array:
    """Sum an int8-quantised bucket over ``axes``; the wire carries int8.

    Compressed reduce-scatter + all-gather (the ring decomposition):
      1. ``all_to_all``: worker d receives every worker's d-th chunk as int8
         (+ an all-gather of the tiny fp32 scales);
      2. local dequantise-and-sum -> fully reduced fp32 shard d;
      3. requantise the shard (fresh per-shard scale) and ``all_gather`` the
         int8 shards back.
    Per-worker wire volume: 2(n-1)/n * size int8 words -- 4x less than the
    fp32 ring.  Must run inside shard_map with ``axes`` bound.  Returns the
    fp32 SUM (same contract as ``psum``), identical on every worker.
    """
    name = axes[0] if len(tuple(axes)) == 1 else tuple(axes)
    n = _group_size(tuple(axes))
    if n == 1:
        return dequantize_int8(q, scale)
    size = q.size
    pad = (-size) % n
    q2d = jnp.pad(q, (0, pad)).reshape(n, -1)
    shards = jax.lax.all_to_all(q2d, name, split_axis=0, concat_axis=0,
                                tiled=True)                      # (n, m) int8
    scales = jax.lax.all_gather(scale, name).reshape(-1)         # (n,) f32
    partial = jnp.sum(shards.astype(jnp.float32) * scales[:, None], axis=0)
    q2, s2 = quantize_int8(partial)
    qg = jax.lax.all_gather(q2, name, tiled=True)                # (n*m,) int8
    s2g = jax.lax.all_gather(s2, name).reshape(-1)               # (n,) f32
    out = (qg.reshape(n, -1).astype(jnp.float32) * s2g[:, None]).reshape(-1)
    return out[:size]


def _tree_flat_views(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def compressed_reduce_gradients(
        grads: Any, err: Any, *, strategy: str, mode: str,
        data_axes: Sequence[str], pod_axis: Optional[str] = None,
        bucket_bytes: int = 25 * 2 ** 20) -> Tuple[Any, Any, jax.Array]:
    """Error-feedback compressed all-reduce of ``grads`` inside shard_map.

    ``grads`` must already be in true (unscaled) gradient units so the
    residual survives AMP loss-scale changes.  Returns
    ``(summed_grads, new_err, finite)`` where ``summed_grads`` follows the
    ``psum`` contract (caller divides by world size), ``new_err`` is the
    local quantisation residual to carry into the next step, and ``finite``
    is the *global* all-workers-finite flag (non-finite workers contribute
    zeros and the residual is held unchanged).
    """
    assert mode in ("fp16", "int8"), mode
    data_axes = tuple(data_axes)
    axes = data_axes + ((pod_axis,) if pod_axis else ())
    world = _group_size(axes)

    fin = jnp.equal(
        jax.lax.psum(all_finite(grads).astype(jnp.int32), axes), world)
    x = jax.tree_util.tree_map(
        lambda g, e: jnp.where(fin, g.astype(jnp.float32), 0.0) + e,
        grads, err)

    if mode == "fp16":
        xc = jax.tree_util.tree_map(lambda v: v.astype(jnp.float16), x)
        new_err = jax.tree_util.tree_map(
            lambda v, c: v - c.astype(jnp.float32), x, xc)
        hier_ok = strategy == "hierarchical" and pod_axis is not None
        red = reduce_gradients(
            xc, strategy=strategy if strategy != "hierarchical" or hier_ok
            else "psum",
            data_axes=data_axes, pod_axis=pod_axis, bucket_bytes=bucket_bytes)
        red = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), red)
    else:
        leaves, treedef = _tree_flat_views(x)
        red_leaves = [None] * len(leaves)
        err_leaves = [None] * len(leaves)
        for bucket in bucket_leaves(x, bucket_bytes):
            flat = jnp.concatenate(
                [leaves[i].reshape(-1) for i in bucket])
            q, scale = quantize_int8(flat)
            local_deq = dequantize_int8(q, scale)
            red_flat = int8_two_stage_all_reduce(q, scale, axes)
            err_flat = flat - local_deq
            off = 0
            for i in bucket:
                sz = leaves[i].size
                red_leaves[i] = red_flat[off:off + sz].reshape(
                    leaves[i].shape)
                err_leaves[i] = err_flat[off:off + sz].reshape(
                    leaves[i].shape)
                off += sz
        red = jax.tree_util.tree_unflatten(treedef, red_leaves)
        new_err = jax.tree_util.tree_unflatten(treedef, err_leaves)

    # a skipped (non-finite) step must not advance the feedback buffer
    new_err = jax.tree_util.tree_map(
        lambda ne, e: jnp.where(fin, ne, e), new_err, err)
    return red, new_err, fin


def exchange_bytes_per_step(n_params: int, *, strategy: str,
                            compression: str = "none", world: int = 1,
                            pod: int = 1,
                            bucket_bytes: int = 25 * 2 ** 20) -> float:
    """Analytic per-worker gradient-exchange wire bytes for one step.

    The roofline/benchmark accounting behind BENCH_train.json: a ring (or
    the equivalent reduce-scatter + all-gather pair) moves 2(n-1)/n words
    per worker; hierarchical moves full-rate words on the fast link but only
    the 1/n_fast shard across pods; int8 adds two fp32 scales per bucket per
    hop-direction.  ``world`` is the total number of workers (including the
    ``pod`` factor for hierarchical).

    The volume is SCHEDULE-independent: the overlapped drain schedule
    (``overlapped_reduce_tree``) moves exactly these bytes, just hidden
    behind the last micro-batch's backward -- whether they land on the step
    critical path is the roofline model's ``overlap_window`` term
    (benchmarks/fig3_weak_scaling.eff_from), not a byte count.  (A schedule
    that instead exchanged per-micro-batch partial sums would inflate this
    by x(A+1)/2 -- one reason the drain schedule is the right overlap.)
    """
    if world <= 1:
        return 0.0
    itemsize = {"none": 4, "fp16": 2, "int8": 1}[compression]
    n_buckets = max(1, -(-n_params * 4 // bucket_bytes))
    scale_overhead = 2 * 4 * n_buckets if compression == "int8" else 0
    if strategy == "hierarchical" and pod > 1 and compression != "int8":
        # int8's wire schedule is strategy-independent (flat two-stage
        # exchange over all axes) -- it falls through to the flat formula
        fast = world // pod
        fast_bytes = 2 * (fast - 1) / fast * n_params * itemsize
        slow_bytes = 2 * (pod - 1) / pod * (n_params / max(fast, 1)) * itemsize
        return fast_bytes + slow_bytes
    return 2 * (world - 1) / world * n_params * itemsize + scale_overhead
