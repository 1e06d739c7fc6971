"""Mixture-of-Experts FFN with expert parallelism.

Three functionally-equivalent implementations (property-tested against each
other in tests/test_moe.py):

  * ``dense``      -- every expert applied to every token, combined by the
                      router weights.  O(E) FLOPs; the correctness oracle.
  * ``replicated`` -- tokens replicated over the 'model' axis; each shard
                      computes only its local experts' tokens and the outputs
                      are psum-combined.  No all-to-all; comm = one psum of
                      activations.  This is the closest analogue of the
                      paper's pure-data-parallel world view (baseline in
                      EXPERIMENTS.md §Perf).
  * ``a2a``        -- canonical expert parallelism: tokens are sharded over
                      the 'model' axis too, routed via ``lax.all_to_all`` to
                      the shard owning their expert, processed, and routed
                      back.  Comm = 2 x (top_k/E-fraction of activations) --
                      the optimized configuration.

Routing uses top-k with per-(shard, expert) capacity C; overflowing tokens
are dropped (standard Switch/GShard semantics).  The load-balance auxiliary
loss (Switch eq. 4) is returned for the trainer to add.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.amp import Policy
from repro.sharding import EMBED, EXPERTS, FF, current_mesh, current_rules
from repro.models.layers import trunc_normal
from repro.utils import ceil_div

Params = Any


def init_moe(key, cfg: ModelConfig) -> Tuple[Params, Any]:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    std_o = 0.02 / math.sqrt(2 * cfg.n_layers)
    params = {
        "router": trunc_normal(ks[0], (d, e)),
        "wi": trunc_normal(ks[1], (e, d, f)),
        "wg": trunc_normal(ks[2], (e, d, f)),
        "wo": trunc_normal(ks[3], (e, f, d), stddev=std_o),
    }
    specs = {
        "router": (EMBED, None),
        "wi": (EXPERTS, EMBED, FF),
        "wg": (EXPERTS, EMBED, FF),
        "wo": (EXPERTS, FF, EMBED),
    }
    return params, specs


def _router(params, xt: jax.Array, cfg: ModelConfig, policy: Policy):
    """xt: (T, d) -> (probs (T,E) f32, topk_idx (T,k), topk_w (T,k) f32, aux)."""
    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, cfg.top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    f_e = jnp.zeros((e,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(topk_idx.size, 1)
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return probs, topk_idx, topk_w, aux


def _expert_ffn(wi, wg, wo, x, cfg: ModelConfig, policy: Policy):
    """x: (E, C, d) grouped tokens; weights (E, d, f) / (E, f, d)."""
    cd = policy.compute_dtype
    hi = jnp.einsum("ecd,edf->ecf", x.astype(cd), wi.astype(cd))
    hg = jnp.einsum("ecd,edf->ecf", x.astype(cd), wg.astype(cd))
    act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
    h = act(hg) * hi
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(cd))


def _dispatch_indices(topk_idx: jax.Array, n_experts: int, capacity: int):
    """Compute scatter destinations for (T*k,) expert assignments.

    Returns (dest (T*k,), keep (T*k,)) where dest in [0, E*C) for kept
    slots and E*C (dump slot) for dropped ones.
    """
    tk = topk_idx.reshape(-1)                     # (T*k,)
    order = jnp.argsort(tk, stable=True)          # sorted by expert
    sorted_e = tk[order]
    # rank within each expert group
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_experts))
    rank = jnp.arange(tk.size) - starts[sorted_e]
    keep_sorted = rank < capacity
    dest_sorted = jnp.where(keep_sorted, sorted_e * capacity + rank,
                            n_experts * capacity)
    inv = jnp.argsort(order, stable=True)
    return dest_sorted[inv], keep_sorted[inv]


def _group_local(xt, topk_idx, topk_w, n_experts, capacity):
    """Group (T,d) tokens into (E, C, d) expert buffers + combine metadata."""
    t, d = xt.shape
    k = topk_idx.shape[-1]
    dest, keep = _dispatch_indices(topk_idx, n_experts, capacity)
    buf = jnp.zeros((n_experts * capacity + 1, d), xt.dtype)
    src = jnp.repeat(xt, k, axis=0)               # (T*k, d) token per slot
    buf = buf.at[dest].set(src)
    grouped = buf[:-1].reshape(n_experts, capacity, d)
    return grouped, dest, keep


def _combine_local(processed, dest, keep, topk_w, t, k, d):
    """Inverse of _group_local: (E,C,d) -> (T,d) weighted combine."""
    flat = processed.reshape(-1, d)
    flat = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)], axis=0)
    slot_out = flat[jnp.where(keep, dest, flat.shape[0] - 1)]   # (T*k, d)
    slot_out = slot_out * topk_w.reshape(-1, 1).astype(slot_out.dtype)
    return slot_out.reshape(t, k, d).sum(axis=1)


def moe_dense(params, x: jax.Array, cfg: ModelConfig, policy: Policy):
    """Oracle: run all experts on all tokens (no drops, no parallelism)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    probs, topk_idx, topk_w, aux = _router(params, xt, cfg, policy)
    cd = policy.compute_dtype
    hi = jnp.einsum("td,edf->tef", xt.astype(cd), params["wi"].astype(cd))
    hg = jnp.einsum("td,edf->tef", xt.astype(cd), params["wg"].astype(cd))
    act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
    h = act(hg) * hi
    out_e = jnp.einsum("tef,efd->ted", h, params["wo"].astype(cd))
    w = jnp.zeros((xt.shape[0], cfg.n_experts), cd).at[
        jnp.arange(xt.shape[0])[:, None], topk_idx].set(topk_w.astype(cd))
    out = jnp.einsum("ted,te->td", out_e, w)
    return out.reshape(b, s, d), aux


def _moe_single(params, xt, cfg: ModelConfig, policy: Policy,
                capacity_factor: float):
    """Capacity-grouped MoE on one shard (the shard_map-free path)."""
    t, d = xt.shape
    c = max(1, ceil_div(int(t * cfg.top_k * capacity_factor), cfg.n_experts))
    probs, topk_idx, topk_w, aux = _router(params, xt, cfg, policy)
    grouped, dest, keep = _group_local(xt, topk_idx, topk_w, cfg.n_experts, c)
    processed = _expert_ffn(params["wi"], params["wg"], params["wo"],
                            grouped, cfg, policy)
    out = _combine_local(processed, dest, keep, topk_w, t, cfg.top_k, d)
    return out, aux


def moe_apply(params, x: jax.Array, cfg: ModelConfig, policy: Policy, *,
              impl: str = "a2a", capacity_factor: Optional[float] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN entry point.  x: (B, S, d).  Returns (y, aux_loss)."""
    capacity_factor = capacity_factor or cfg.capacity_factor
    mesh = current_mesh()
    b, s, d = x.shape
    if impl == "dense" or mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] == 1:
        if impl == "dense":
            return moe_dense(params, x, cfg, policy)
        out, aux = _moe_single(params, x.reshape(-1, d), cfg, policy,
                               capacity_factor)
        return out.reshape(b, s, d), aux

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # batch=1 shapes (long_500k decode) cannot shard over the data axes:
    # replicate the tokens instead (each data row repeats the tiny compute)
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]
    if b % data_size != 0:
        data_axes = ()
    m = mesh.shape["model"]
    e_local = cfg.n_experts // m if cfg.n_experts % m == 0 else 0

    if e_local == 0 or impl == "replicated":
        # experts not evenly shardable (e.g. granite's 40 on 16) or the
        # baseline impl: replicate tokens over 'model', shard the FF dim.
        return _moe_replicated(params, x, cfg, policy, capacity_factor,
                               mesh, data_axes)
    if impl == "a2a":
        return _moe_a2a(params, x, cfg, policy, capacity_factor, mesh,
                        data_axes, m, e_local)
    raise ValueError(f"unknown moe impl {impl!r}")


def _batch_spec(data_axes):
    """PartitionSpec entry for the batch dim given the (possibly empty)
    effective data axes."""
    if not data_axes:
        return None
    return data_axes if len(data_axes) > 1 else data_axes[0]


def _moe_replicated(params, x, cfg, policy, capacity_factor, mesh, data_axes):
    """Tokens replicated over 'model'; each shard computes its local experts.

    Works for any E (non-divisible E handled by padding the expert dim).
    Comm: one psum of the (B,S,d) output over 'model'.
    """
    m = mesh.shape["model"]
    e_pad = ceil_div(cfg.n_experts, m) * m
    b, s, d = x.shape
    batch_spec = _batch_spec(data_axes)

    def pad_e(p, axis):
        pads = [(0, 0)] * p.ndim
        pads[axis] = (0, e_pad - cfg.n_experts)
        return jnp.pad(p, pads)

    wi = pad_e(params["wi"], 0)
    wg = pad_e(params["wg"], 0)
    wo = pad_e(params["wo"], 0)
    router = params["router"]

    def local_fn(xl, router, wi, wg, wo):
        # xl: (B_loc, S, d) -- replicated over 'model'
        t_loc = xl.shape[0] * xl.shape[1]
        xt = xl.reshape(-1, d)
        probs, topk_idx, topk_w, aux = _router(
            {"router": router}, xt, cfg, policy)
        c = max(1, ceil_div(int(t_loc * cfg.top_k * capacity_factor), e_pad))
        grouped, dest, keep = _group_local(xt, topk_idx, topk_w, e_pad, c)
        # keep only this shard's experts
        e_loc = e_pad // m
        shard = jax.lax.axis_index("model")
        local_grp = jax.lax.dynamic_slice_in_dim(
            grouped, shard * e_loc, e_loc, axis=0)
        processed_local = _expert_ffn(wi, wg, wo, local_grp, cfg, policy)
        processed = jnp.zeros((e_pad, c, d), processed_local.dtype)
        processed = jax.lax.dynamic_update_slice_in_dim(
            processed, processed_local, shard * e_loc, axis=0)
        out = _combine_local(processed, dest, keep, topk_w, t_loc,
                             cfg.top_k, d)
        out = jax.lax.psum(out, "model")
        # aux is computed from model-replicated tokens: varies on data only
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        return out.reshape(xl.shape), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_spec, None, None), P()),
    )
    return fn(x, router, wi, wg, wo)


def _moe_a2a(params, x, cfg, policy, capacity_factor, mesh, data_axes,
             m, e_local):
    """Canonical expert-parallel all-to-all MoE (train/prefill path).

    Tokens sharded over (data..., model) -- sequence dim carries the 'model'
    shard.  Each shard routes its local tokens, all_to_all ships the (E, C)
    buffers to expert owners, experts run, reverse all_to_all ships results
    back.  Comm per direction: E*C*d bytes vs the replicated impl's full
    activation psum.
    """
    b, s, d = x.shape
    batch_spec = _batch_spec(data_axes)
    if s % m != 0:
        # decode / tiny seq: fall back to replicated
        return _moe_replicated(params, x, cfg, policy, capacity_factor,
                               mesh, data_axes)

    def local_fn(xl, router, wi, wg, wo):
        # xl: (B_loc, S/m, d); wi/wg/wo: (E_loc, ...)
        bl, sl, _ = xl.shape
        t_loc = bl * sl
        xt = xl.reshape(-1, d)
        probs, topk_idx, topk_w, aux = _router(
            {"router": router}, xt, cfg, policy)
        c = max(1, ceil_div(int(t_loc * cfg.top_k * capacity_factor),
                            cfg.n_experts))
        grouped, dest, keep = _group_local(
            xt, topk_idx, topk_w, cfg.n_experts, c)   # (E, C, d)
        # ship: expert e lives on shard e // e_local.  Chunk m ways on the
        # expert dim; all_to_all exchanges chunk i <-> shard i.
        recv = jax.lax.all_to_all(grouped, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: (m * e_local, c, d) = for local experts, per source shard
        recv = recv.reshape(m, e_local, c, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(e_local, m * c, d)
        processed = _expert_ffn(wi, wg, wo, recv, cfg, policy)
        processed = processed.reshape(e_local, m, c, d).transpose(1, 0, 2, 3)
        processed = processed.reshape(m * e_local, c, d)
        back = jax.lax.all_to_all(processed, "model", split_axis=0,
                                  concat_axis=0, tiled=True)  # (E, C, d)
        out = _combine_local(back, dest, keep, topk_w, t_loc, cfg.top_k, d)
        aux = jax.lax.pmean(aux, data_axes + ("model",))
        return out.reshape(bl, sl, d), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_spec, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_spec, "model", None), P()),
    )
    return fn(x, params["router"], params["wi"], params["wg"], params["wo"])
