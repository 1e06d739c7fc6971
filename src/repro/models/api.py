"""Model API facade: losses, synthetic batches, dry-run input specs.

Everything the training loop / serving loop / dry-run needs per architecture:
  * ``lm_train_loss`` / BERT's loss  (loss_fn(params, batch) -> (loss, aux))
  * ``train_batch_struct``  -- ShapeDtypeStructs for the (arch x shape) pair
  * ``make_synth_batch``    -- concrete random batch (smoke tests / benches)
  * ``batch_logical_axes``  / ``state_logical_axes`` -- sharding spec trees
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core.amp import Policy
from repro.models import bert as BERT
from repro.models import transformer as T
from repro.sharding import (BATCH, EMBED, HEADS, INNER, KV_HEADS, KV_SEQ,
                            LAYERS, VOCAB)

Struct = jax.ShapeDtypeStruct


def mlm_positions_count(seq_len: int) -> int:
    """Paper Table 6: 20 predictions at S=128, 80 at S=512 -- 5/32 of the
    sequence, the 15% masking rate rounded up to a whole ratio."""
    return max(1, seq_len * 5 // 32)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def lm_train_loss(params, batch, cfg: ModelConfig, policy: Policy, *,
                  moe_impl: str = "a2a", remat: bool = False,
                  aux_coef: Optional[float] = None):
    """Next-token cross-entropy for decoder-style architectures."""
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    kw = {}
    if cfg.is_encoder_decoder:
        kw["enc_frames"] = batch["frames"]
    if cfg.n_vision_tokens:
        kw["vision_embeds"] = batch["vision"]
    logits, aux = T.apply_lm(params, inputs, cfg, policy, moe_impl=moe_impl,
                             remat=remat, **kw)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    coef = cfg.router_aux_coef if aux_coef is None else aux_coef
    if cfg.has_moe:
        loss = loss + coef * aux
    return loss, {"lm_loss": nll.mean(), "router_aux": aux}


def make_loss_fn(cfg: ModelConfig, policy: Policy, *, moe_impl="a2a",
                 remat=False):
    if cfg.is_encoder_only:
        def loss_fn(params, batch):
            return BERT.bert_pretrain_loss(params, batch, cfg, policy,
                                           remat=remat)
    else:
        def loss_fn(params, batch):
            return lm_train_loss(params, batch, cfg, policy,
                                 moe_impl=moe_impl, remat=remat)
    return loss_fn


def init_params(key, cfg: ModelConfig):
    if cfg.is_encoder_only:
        return BERT.init_bert(key, cfg)
    return T.init_model(key, cfg)


def abstract_params(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical-spec tree) without allocating.

    Init runs under eval_shape; the spec tree (plain Python tuples) is
    captured from the traced call since strings cannot be eval_shape outputs.
    """
    box = {}

    def f(key):
        p, s = init_params(key, cfg)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box["specs"]


# ---------------------------------------------------------------------------
# Batch construction
# ---------------------------------------------------------------------------

def train_batch_struct(cfg: ModelConfig, shape: InputShape) -> Dict[str, Struct]:
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_encoder_only:
        p = mlm_positions_count(s)
        return {
            "tokens": Struct((b, s), jnp.int32),
            "type_ids": Struct((b, s), jnp.int32),
            "mlm_positions": Struct((b, p), jnp.int32),
            "mlm_labels": Struct((b, p), jnp.int32),
            "nsp_labels": Struct((b,), jnp.int32),
        }
    out = {"tokens": Struct((b, s + 1), jnp.int32)}
    if cfg.is_encoder_decoder:
        out["frames"] = Struct((b, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.n_vision_tokens:
        out["vision"] = Struct((b, cfg.n_vision_tokens, cfg.d_model),
                               jnp.float32)
    return out


def batch_logical_axes(cfg: ModelConfig, batch_tree) -> Any:
    """Logical-axis spec tree matching a train batch."""
    def spec_for(name, leaf):
        axes = [BATCH] + [None] * (len(leaf.shape) - 1)
        return tuple(axes)
    return {k: spec_for(k, v) for k, v in batch_tree.items()}


def make_synth_batch(key, cfg: ModelConfig, shape: InputShape
                     ) -> Dict[str, jax.Array]:
    """Concrete random batch with the right statistics (smoke/benchmarks)."""
    structs = train_batch_struct(cfg, shape)
    ks = jax.random.split(key, len(structs))
    out = {}
    for (name, st), k in zip(sorted(structs.items()), ks):
        if st.dtype == jnp.int32:
            if name == "nsp_labels":
                out[name] = jax.random.randint(k, st.shape, 0, 2)
            elif name == "mlm_positions":
                out[name] = jnp.broadcast_to(
                    jnp.arange(st.shape[-1], dtype=jnp.int32)[None], st.shape)
            elif name == "type_ids":
                out[name] = jnp.zeros(st.shape, jnp.int32)
            elif name == "mlm_labels":
                out[name] = jax.random.randint(k, st.shape, 0, cfg.vocab_size)
            else:
                out[name] = jax.random.randint(k, st.shape, 0, cfg.vocab_size)
        else:
            out[name] = 0.1 * jax.random.normal(k, st.shape, st.dtype)
    return out


# ---------------------------------------------------------------------------
# Serving structs
# ---------------------------------------------------------------------------

def prefill_batch_struct(cfg: ModelConfig, shape: InputShape):
    b, s = shape.global_batch, shape.seq_len
    out = {"tokens": Struct((b, s), jnp.int32)}
    if cfg.is_encoder_decoder:
        out["frames"] = Struct((b, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.n_vision_tokens:
        out["vision"] = Struct((b, cfg.n_vision_tokens, cfg.d_model),
                               jnp.float32)
    return out


def decode_state_struct(cfg: ModelConfig, shape: InputShape,
                        cache_dtype=jnp.bfloat16):
    b, s = shape.global_batch, shape.seq_len
    enc_len = cfg.enc_seq if cfg.is_encoder_decoder else 0
    return jax.eval_shape(
        lambda: T.init_decode_state(cfg, b, s, cache_dtype, enc_len=enc_len))


def decode_batch_struct(cfg: ModelConfig, shape: InputShape):
    return {"token": Struct((shape.global_batch, 1), jnp.int32)}


def state_logical_axes(cfg: ModelConfig, state_tree) -> Any:
    """Spec tree for a decode state: caches (LAYERS, BATCH, KV_SEQ, KV, Dh);
    mamba/rwkv states sharded on batch + inner/heads."""
    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        nd = len(leaf.shape)
        if "pos" in names:
            return (BATCH,)[:nd]  # (B,) per-slot decode positions
        # paged caches: block tables are gathered host-side by page id, so
        # the pool is replicated apart from the kv-head axis; tables follow
        # the batch axis like every other per-slot leaf
        if "k_pages" in names or "v_pages" in names:
            return (LAYERS, None, None, KV_HEADS, None)[:nd]
        if "block_table" in names:
            return (LAYERS, BATCH, None)[:nd]
        if "k_scale" in names or "v_scale" in names:
            return (LAYERS, None, KV_HEADS)[:nd]
        if "cache" in names or "cross" in names:
            return (LAYERS, BATCH, KV_SEQ, KV_HEADS, None)[:nd]
        if "conv" in names:
            return (LAYERS, BATCH, None, INNER)[:nd]
        if "ssm" in names:
            return (LAYERS, BATCH, INNER, None)[:nd]
        if "wkv" in names:
            return (LAYERS, BATCH, HEADS, None, None)[:nd]
        if "tm_shift" in names or "cm_shift" in names:
            return (LAYERS, BATCH, None, None)[:nd]
        return (LAYERS, BATCH) + (None,) * (nd - 2)

    return jax.tree_util.tree_map_with_path(spec, state_tree)


def long_context_supported(cfg: ModelConfig) -> bool:
    """DESIGN.md §4: long_500k runs only for sub-quadratic-capable archs."""
    return cfg.subquadratic and not cfg.is_encoder_decoder \
        and not cfg.is_encoder_only


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """(supported, reason_if_not) for an (arch, input-shape) pair."""
    if cfg.is_encoder_only and shape.kind != "train":
        return False, "encoder-only (BERT): no prefill/decode step exists"
    if shape.name == "long_500k" and not long_context_supported(cfg):
        if cfg.is_encoder_decoder:
            return False, ("whisper: enc-dec, full-attention decoder and "
                           "<=30s architectural audio context")
        return False, ("pure full-attention arch without sliding-window/"
                       "block-sparse variant (DESIGN.md carve-out)")
    return True, ""
