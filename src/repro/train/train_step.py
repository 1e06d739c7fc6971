"""Training step: the paper's optimization stack composed.

    loss -> [dynamic loss scale] -> grad over [accum_steps microbatches]
         -> [gradient collective: psum | ring | hierarchical | bucketed]
         -> unscale -> clip -> [LAMB | AdamW] with fp32 master weights

Two distribution modes:

  * ``gspmd``   -- one ``jax.jit`` over the whole step with NamedShardings;
                   XLA inserts gradient reduce-scatters/all-reduces.  Used
                   for tensor/expert/FSDP-sharded architectures (all ten
                   assigned archs at production scale).
  * ``dp_shardmap`` -- paper-faithful pure data parallelism: ``shard_map``
                   over the data axes with the model replicated and the
                   gradient exchange done EXPLICITLY via
                   core/collectives.reduce_gradients (psum / NCCL-style
                   ppermute ring / hierarchical / bucketed-overlap).  This is
                   the mode the paper's BERT runs use, and the ring/
                   hierarchical HLO is inspectable in the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.core import collectives as C
from repro.core.amp import (LossScaleState, Policy, make_loss_scale,
                            make_policy)
from repro.core.grad_accum import accumulate_gradients
from repro.models import api
from repro.optim import adamw_update, lamb_init, lamb_update, warmup_poly_decay
from repro.optim.lamb import LambState
from repro.sharding import (ShardingRules, make_rules, resolve_spec,
                            use_sharding_ctx)
from repro.utils import all_finite, global_norm


class TrainState(NamedTuple):
    opt: LambState
    loss_scale: LossScaleState
    # error-feedback residual for the compressed gradient exchange
    # (grad_compression != "none"): each worker's OWN quantisation error
    # carried into its next step's gradients.  The residual is inherently
    # per-worker (local compression error), so leaves carry a leading
    # ``world`` dim sharded over the DP axes -- a checkpoint then holds
    # every worker's residual and exact-resume stays bit-identical
    # (declaring it replicated would silently keep divergent per-device
    # buffers under check_vma=False and checkpoint only device 0's).
    # None when compression is off, so the checkpoint tree (PR 7
    # manifest) is unchanged for existing runs.
    err: Any = None


def init_train_state(params, policy: Policy, tcfg: TrainConfig,
                     world: int = 1) -> TrainState:
    ls = make_loss_scale(policy).init()
    err = None
    if tcfg.grad_compression != "none":
        err = jax.tree_util.tree_map(
            lambda p: jnp.zeros((world,) + tuple(p.shape), jnp.float32),
            params)
    return TrainState(lamb_init(params), ls, err)


def _optimizer_update(grads, opt: LambState, tcfg: TrainConfig, *,
                      skip_update):
    lr = warmup_poly_decay(opt.step + 1, base_lr=tcfg.learning_rate,
                           warmup_steps=tcfg.warmup_steps,
                           total_steps=tcfg.total_steps)
    if tcfg.optimizer == "lamb":
        return lamb_update(grads, opt, lr=lr, wd=tcfg.weight_decay,
                           skip_update=skip_update), lr
    return adamw_update(grads, opt, lr=lr, wd=tcfg.weight_decay,
                        skip_update=skip_update), lr


def _clip_grads(grads, max_norm: float):
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gnorm


def train_step_fn(state: TrainState, batch, *, cfg: ModelConfig,
                  tcfg: TrainConfig, policy: Policy,
                  grad_reduce: Optional[Callable] = None,
                  metric_reduce: Optional[Callable] = None,
                  grad_constraint: Optional[Callable] = None,
                  grad_exchange: Optional[Callable] = None,
                  overlap_reduce: Optional[Callable] = None):
    """Shared step body.  ``grad_reduce``: None under GSPMD (implicit).

    ``grad_exchange``: the compressed exchange (DP mode only).  Called as
    ``(unscaled_grads, err) -> (mean_grads, new_err, finite)``; it replaces
    the reduce+unscale+finite sequence for gradients -- unscaling happens
    *before* the exchange so the error-feedback residual lives in true
    gradient units and survives AMP loss-scale changes between steps.

    ``overlap_reduce``: the uncompressed overlapped drain exchange (DP mode,
    ``tcfg.overlap_exchange``).  Called as ``(local_grad_sum, inv_accum) ->
    mean_grads`` INSIDE accumulate_gradients' flat last-micro-batch region
    (core/collectives.overlapped_reduce_tree); grads come back already
    reduced and averaged, still in loss-scaled units, so the unscale ->
    finite sequence below matches the serial path bit for bit.  When
    ``tcfg.overlap_exchange`` is set with compression on, the compressed
    ``grad_exchange`` itself is moved into the drain region instead (same
    ops as the serial compressed path, so losses stay bit-identical).
    """
    loss_scale = make_loss_scale(policy)
    loss_fn = api.make_loss_fn(cfg, policy, moe_impl=tcfg.moe_impl,
                               remat=tcfg.remat)

    compute_params = policy.cast_params(state.opt.master)
    if tcfg.pure_dp:
        # ZeRO-1: optimizer state stays sharded; the bf16 compute copy is
        # all-gathered ONCE per step (outside the block scan) and every
        # device runs pure data parallelism over the whole mesh.
        from repro.sharding import current_mesh
        mesh = current_mesh()
        if mesh is not None:
            repl = NamedSharding(mesh, P())
            compute_params = jax.tree_util.tree_map(
                lambda p: jax.lax.with_sharding_constraint(p, repl),
                compute_params)

    def scaled_loss(p, b):
        loss, metrics = loss_fn(p, b)
        return loss_scale.scale_loss(loss, state.loss_scale), metrics

    overlap = tcfg.overlap_exchange and (
        overlap_reduce is not None or grad_exchange is not None)
    exchange_hook = None
    if overlap and grad_exchange is not None:
        def exchange_hook(grad_sum, inv):
            # same op sequence as the serial compressed path (mean ->
            # unscale -> compressed exchange), just issued in the drain
            # region -- compressed overlap losses are bit-identical too
            g = grad_sum if inv is None else jax.tree_util.tree_map(
                lambda v: v * inv, grad_sum)
            g = loss_scale.unscale_grads(g, state.loss_scale)
            return grad_exchange(g, state.err)
    elif overlap:
        exchange_hook = overlap_reduce

    loss, grads, metrics = accumulate_gradients(
        scaled_loss, compute_params, batch, tcfg.accum_steps,
        grad_constraint=grad_constraint, exchange=exchange_hook)

    new_err = state.err
    if overlap and grad_exchange is not None:
        grads, new_err, finite = grads
        if grad_reduce is not None:
            loss = grad_reduce(loss)
        loss = loss / state.loss_scale.scale
    elif overlap:
        # grads arrive reduced+averaged (loss-scaled); finish exactly as
        # the serial uncompressed path does after its reduce
        if grad_reduce is not None:
            loss = grad_reduce(loss)
        grads = loss_scale.unscale_grads(grads, state.loss_scale)
        loss = loss / state.loss_scale.scale
        finite = all_finite(grads)
    elif grad_exchange is not None:
        # compressed path: unscale locally first, then exchange compressed
        # bytes with error feedback (the flag comes back globally reduced)
        grads = loss_scale.unscale_grads(grads, state.loss_scale)
        grads, new_err, finite = grad_exchange(grads, state.err)
        if grad_reduce is not None:
            loss = grad_reduce(loss)
        loss = loss / state.loss_scale.scale
    else:
        if grad_reduce is not None:
            grads = grad_reduce(grads)
            loss = grad_reduce(loss)
        grads = loss_scale.unscale_grads(grads, state.loss_scale)
        loss = loss / state.loss_scale.scale
        finite = all_finite(grads)
    if metric_reduce is not None:
        metrics = metric_reduce(metrics)

    new_ls, _ = loss_scale.update(state.loss_scale, finite)
    grads, gnorm = _clip_grads(grads, tcfg.grad_clip)
    new_opt, lr = _optimizer_update(grads, state.opt, tcfg,
                                    skip_update=jnp.logical_not(finite))
    out_metrics = {
        "loss": loss.astype(jnp.float32),
        "grad_norm": gnorm,
        "lr": lr,
        "loss_scale": new_ls.scale,
        "skipped": jnp.logical_not(finite),
    }
    for k, v in metrics.items():
        out_metrics[k] = v.astype(jnp.float32) if hasattr(v, "astype") else v
    return TrainState(new_opt, new_ls, new_err), out_metrics


# ---------------------------------------------------------------------------
# GSPMD mode
# ---------------------------------------------------------------------------

def state_shardings(param_specs, param_shapes, mesh: Mesh,
                    rules: ShardingRules) -> TrainState:
    """NamedSharding tree for TrainState given param logical specs."""
    def shard_tree(shapes):
        return jax.tree_util.tree_map(
            lambda spec, shp: NamedSharding(
                mesh, resolve_spec(shp.shape, spec, rules, mesh)),
            param_specs, shapes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    repl = NamedSharding(mesh, P())
    opt = LambState(step=repl, master=shard_tree(param_shapes),
                    m=shard_tree(param_shapes), v=shard_tree(param_shapes))
    ls = LossScaleState(repl, repl, repl)
    return TrainState(opt, ls)


def batch_shardings(cfg: ModelConfig, batch_tree, mesh: Mesh,
                    rules: ShardingRules):
    axes = api.batch_logical_axes(cfg, batch_tree)
    return jax.tree_util.tree_map(
        lambda spec, leaf: NamedSharding(
            mesh, resolve_spec(leaf.shape, spec, rules, mesh)),
        axes, batch_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def make_train_step_gspmd(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                          rules: ShardingRules, param_specs, param_shapes,
                          shape: InputShape):
    """jit'd (state, batch) -> (state, metrics) with explicit shardings.

    Place the initial state with ``state_shardings`` before the first call
    so the step compiles once."""
    policy = make_policy(tcfg.precision)
    st_shard = state_shardings(param_specs, param_shapes, mesh, rules)
    b_struct = api.train_batch_struct(cfg, shape)
    b_shard = batch_shardings(cfg, b_struct, mesh, rules)

    if tcfg.grad_compression != "none":
        raise ValueError(
            "grad_compression requires the explicit-collective pure-DP "
            "shard_map mode (make_train_step_dp); GSPMD's implicit "
            "reduces cannot carry compressed bytes")
    if tcfg.overlap_exchange:
        raise ValueError(
            "overlap_exchange requires the explicit-collective pure-DP "
            "shard_map mode (make_train_step_dp); GSPMD owns its own "
            "reduce schedule and cannot take the drain-region collectives")

    grad_constraint = None
    if tcfg.shard_grads:
        def grad_constraint(grads):
            return jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, st_shard.opt.master)

    def train_step(state, batch):
        with use_sharding_ctx(mesh, rules):
            return train_step_fn(state, batch, cfg=cfg, tcfg=tcfg,
                                 policy=policy,
                                 grad_constraint=grad_constraint)

    metrics_shard = None  # let XLA pick (replicated scalars)
    return jax.jit(train_step,
                   in_shardings=(st_shard, b_shard),
                   out_shardings=(st_shard, metrics_shard),
                   donate_argnums=(0,)), b_struct


# ---------------------------------------------------------------------------
# Paper-faithful pure-DP mode (BERT): shard_map + explicit collectives
# ---------------------------------------------------------------------------

def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the DP batch is split over: every one of pod/data/model."""
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def _dp_state_specs(state: TrainState, all_axes) -> TrainState:
    # everything replicated except the error-feedback residual, whose
    # leading world dim is sharded so each worker keeps (and the
    # checkpoint records) its own buffer
    err_spec = P(all_axes if len(all_axes) > 1 else all_axes[0])
    return TrainState(
        opt=jax.tree_util.tree_map(lambda _: P(), state.opt),
        loss_scale=jax.tree_util.tree_map(lambda _: P(), state.loss_scale),
        err=jax.tree_util.tree_map(lambda _: err_spec, state.err))


def dp_state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    """The placement ``make_train_step_dp``'s step gives the state it
    returns.  Put the initial state there (``jax.device_put``): a state left
    on the default device compiles the step once for that placement and
    again at step 2 for this one."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        _dp_state_specs(state, _dp_axes(mesh)),
        is_leaf=lambda x: isinstance(x, P))


def make_train_step_dp(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                       shape: InputShape):
    """Pure data parallelism with explicit gradient exchange (paper §4.4).

    Place the initial state with ``dp_state_shardings`` before the first
    call so the step compiles once."""
    policy = make_policy(tcfg.precision)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    all_axes = _dp_axes(mesh)
    # batch is sharded over every mesh axis in DP mode
    world = 1
    for a in all_axes:
        world *= mesh.shape[a]

    strategy = tcfg.collective_strategy

    def reduce_fn(tree):
        if strategy == "local":
            # calibration-only: NO gradient collective (workers diverge!).
            # The timing breakdown (trainer/benchmarks) times this twin to
            # split a measured step into compute_s vs exchange_s.
            red = tree
        elif strategy == "hierarchical" and pod_axis:
            fast = tuple(a for a in all_axes if a != pod_axis)
            red = C.hierarchical_psum_tree(tree, fast, pod_axis)
        elif strategy == "ring":
            name = all_axes[0] if len(all_axes) == 1 else all_axes
            red = C.ring_all_reduce_tree(tree, name)
        elif strategy == "bucketed":
            red = C.bucketed_psum_tree(tree, all_axes,
                                       bucket_bytes=tcfg.bucket_bytes)
        else:
            red = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, all_axes), tree)
        return jax.tree_util.tree_map(lambda g: g / world, red)

    def metric_reduce(metrics):
        # loss_fn aux metrics are per-shard means; make them global
        return jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, all_axes)
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating)
            else v, metrics)

    grad_exchange = None
    if tcfg.grad_compression != "none":
        non_pod = tuple(a for a in all_axes if a != pod_axis)

        def grad_exchange(grads, err):
            # err leaves arrive as this worker's (1, *shape) slice of the
            # world-stacked residual (sharded over the DP axes)
            err_local = jax.tree_util.tree_map(lambda e: e[0], err)
            red, new_err, fin = C.compressed_reduce_gradients(
                grads, err_local, strategy=strategy,
                mode=tcfg.grad_compression,
                data_axes=non_pod, pod_axis=pod_axis,
                bucket_bytes=tcfg.bucket_bytes)
            red = jax.tree_util.tree_map(lambda g: g / world, red)
            new_err = jax.tree_util.tree_map(lambda e: e[None], new_err)
            return red, new_err, fin

    overlap_reduce = None
    if tcfg.overlap_exchange and tcfg.grad_compression == "none":
        non_pod = tuple(a for a in all_axes if a != pod_axis)

        def overlap_reduce(grad_sum, inv):
            return C.overlapped_reduce_tree(
                grad_sum, strategy=strategy, data_axes=non_pod,
                pod_axis=pod_axis, bucket_bytes=tcfg.bucket_bytes,
                world=world, pre_scale=inv)

    def step(state, batch):
        return train_step_fn(state, batch, cfg=cfg, tcfg=tcfg, policy=policy,
                             grad_reduce=reduce_fn,
                             metric_reduce=metric_reduce,
                             grad_exchange=grad_exchange,
                             overlap_reduce=overlap_reduce)

    b_struct = api.train_batch_struct(cfg, shape)
    batch_spec = P(all_axes if len(all_axes) > 1 else all_axes[0])
    batch_specs = jax.tree_util.tree_map(lambda s: batch_spec, b_struct)

    def train_step(state, batch):
        # check_vma=False: the ppermute-ring / psum_scatter+all_gather
        # strategies produce values that are replicated by construction,
        # which the varying-axes type system cannot verify.
        specs = _dp_state_specs(state, all_axes)
        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(specs, batch_specs),
            out_specs=(specs, P()),
            check_vma=False,
        )
        return fn(state, batch)

    return jax.jit(train_step), b_struct
