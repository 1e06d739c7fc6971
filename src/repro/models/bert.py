"""BERT (the paper's model): post-LN encoder + MLM & NSP heads.

Faithful to Devlin et al. as reproduced by Lin et al. 2020:
  * token + learned-position + segment(type) embeddings, embed-LayerNorm
  * post-LayerNorm residual blocks (x = LN(x + sublayer(x)))
  * GELU (the paper's §4.3 fusion example) in the FFN
  * MLM head: dense d->d + GELU + LN + tied decoder + output bias
  * NSP head: tanh pooler on [CLS] + binary classifier
Loss = masked-LM cross-entropy (labels==-100 ignored) + NSP cross-entropy.

Named scopes (op metadata only; the backward pass keeps them as
``transpose(jvp(<scope>))``): ``bert.embed`` (embeddings and their norm),
``bert.attention`` and ``bert.ffn`` (each sublayer with its post-LN
residual norm), ``bert.heads`` (MLM logits, pooler, NSP, both losses).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.amp import Policy
from repro.sharding import EMBED, VOCAB, lshard
from repro.models import layers as L

Params = Any


def init_bert(key, cfg: ModelConfig):
    ks = jax.random.split(key, 10)
    params, specs = {}, {}
    params["embed"], specs["embed"] = L.init_embedding(ks[0], cfg)
    params["embed"]["type"] = L.trunc_normal(ks[1], (2, cfg.d_model))
    specs["embed"]["type"] = (None, EMBED)
    params["embed_norm"], specs["embed_norm"] = L.init_norm(cfg)

    def init_one(k):
        p = {}
        kk = jax.random.split(k, 2)
        p["attn"], _ = L.init_attention(kk[0], cfg)
        p["attn_norm"], _ = L.init_norm(cfg)
        p["mlp"], _ = L.init_mlp(kk[1], cfg)
        p["mlp_norm"], _ = L.init_norm(cfg)
        return p

    _, sa = L.init_attention(ks[2], cfg)
    _, sn = L.init_norm(cfg)
    _, sm = L.init_mlp(ks[2], cfg)
    layer_specs = {"attn": sa, "attn_norm": sn, "mlp": sm, "mlp_norm": sn}
    params["blocks"] = jax.vmap(init_one)(jax.random.split(ks[3], cfg.n_layers))
    specs["blocks"] = jax.tree_util.tree_map(
        lambda s: (None,) + tuple(s), layer_specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))

    # heads
    params["mlm_transform"] = {
        "w": L.trunc_normal(ks[4], (cfg.d_model, cfg.d_model)),
        "b": jnp.zeros((cfg.d_model,))}
    specs["mlm_transform"] = {"w": (EMBED, EMBED), "b": (EMBED,)}
    params["mlm_norm"], specs["mlm_norm"] = L.init_norm(cfg)
    params["mlm_bias"] = jnp.zeros((cfg.vocab_size,))
    specs["mlm_bias"] = (VOCAB,)
    params["pooler"] = {"w": L.trunc_normal(ks[5], (cfg.d_model, cfg.d_model)),
                        "b": jnp.zeros((cfg.d_model,))}
    specs["pooler"] = {"w": (EMBED, EMBED), "b": (EMBED,)}
    params["nsp"] = {"w": L.trunc_normal(ks[6], (cfg.d_model, 2)),
                     "b": jnp.zeros((2,))}
    specs["nsp"] = {"w": (EMBED, None), "b": (None,)}
    return params, specs


def apply_bert(params, tokens, type_ids, cfg: ModelConfig, policy: Policy,
               *, attn_mask: Optional[jax.Array] = None,
               remat: bool = False):
    """Returns (sequence_output (B,S,d), pooled (B,d))."""
    b, s = tokens.shape
    with jax.named_scope("bert.embed"):
        x = L.embed_tokens(params["embed"], tokens, cfg, policy)
        x = x + jnp.take(params["embed"]["type"], type_ids,
                         axis=0).astype(x.dtype)
        x = L.apply_norm(params["embed_norm"], x, cfg, policy)
        x = lshard(x, "batch", None, None)

    def block(x, p):
        # post-LN: x = LN(x + attn(x)); x = LN(x + mlp(x))
        with jax.named_scope("bert.attention"):
            y, _ = L.apply_attention(p["attn"], x, cfg, policy,
                                     mixer_kind="attn_bidir")
            x = L.apply_norm(p["attn_norm"], x + y, cfg, policy)
        with jax.named_scope("bert.ffn"):
            y = L.apply_mlp(p["mlp"], x, cfg, policy)
            x = L.apply_norm(p["mlp_norm"], x + y, cfg, policy)
        return x, None

    body = jax.checkpoint(block) if remat else block
    x, _ = jax.lax.scan(body, x, params["blocks"])

    with jax.named_scope("bert.heads"):
        pooled = jnp.tanh(
            x[:, 0].astype(policy.compute_dtype) @
            params["pooler"]["w"].astype(policy.compute_dtype) +
            params["pooler"]["b"].astype(policy.compute_dtype))
    return x, pooled


def bert_logits(params, seq_out, cfg: ModelConfig, policy: Policy,
                mlm_positions: Optional[jax.Array] = None):
    """MLM logits.  If mlm_positions (B, P) given, gather those positions
    first (the paper's Predictions/S from Table 6 -- avoids the full
    (B,S,V) logits tensor, BERT's standard trick)."""
    cd = policy.compute_dtype
    h = seq_out
    if mlm_positions is not None:
        h = jnp.take_along_axis(
            seq_out, mlm_positions[..., None].astype(jnp.int32), axis=1)
    h = h.astype(cd) @ params["mlm_transform"]["w"].astype(cd) + \
        params["mlm_transform"]["b"].astype(cd)
    h = L.gelu_tanh(h)
    h = L.apply_norm(params["mlm_norm"], h, cfg, policy)
    logits = h.astype(cd) @ params["embed"]["tok"].T.astype(cd) + \
        params["mlm_bias"].astype(cd)
    return lshard(logits, "batch", None, "vocab")


def bert_pretrain_loss(params, batch, cfg: ModelConfig, policy: Policy,
                       *, remat: bool = False):
    """Paper's pre-training objective.

    batch: tokens (B,S) i32, type_ids (B,S) i32, mlm_positions (B,P) i32,
           mlm_labels (B,P) i32 (-100 = unmasked/pad), nsp_labels (B,) i32.
    Returns (loss, metrics dict).
    """
    seq_out, pooled = apply_bert(params, batch["tokens"], batch["type_ids"],
                                 cfg, policy, remat=remat)
    with jax.named_scope("bert.heads"):
        mlm_logits = bert_logits(params, seq_out, cfg, policy,
                                 mlm_positions=batch["mlm_positions"])
        labels = batch["mlm_labels"]
        valid = (labels >= 0)
        lab = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        mlm_loss = jnp.sum(nll * valid) / jnp.maximum(valid.sum(), 1)

        cd = policy.compute_dtype
        nsp_logits = pooled @ params["nsp"]["w"].astype(cd) + \
            params["nsp"]["b"].astype(cd)
        nsp_logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nsp_logp, batch["nsp_labels"][:, None],
                                axis=-1)[:, 0])

        loss = mlm_loss + nsp_loss
        mlm_acc = jnp.sum((mlm_logits.argmax(-1) == lab) * valid) / \
            jnp.maximum(valid.sum(), 1)
        metrics = {"mlm_loss": mlm_loss, "nsp_loss": nsp_loss,
                   "mlm_acc": mlm_acc}
    return loss, metrics
