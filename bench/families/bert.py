"""The BERT family: what the benchmark knows of BERT pre-training.

A family module gives the harness (``bench/run.py``, ``bench/cell.py``,
``bench/reference.py``) everything that depends on the model:

* ``program_config(cfg)``: the program's ``ModelConfig`` for a
  configuration file, after checking that ``init_weights`` has the
  program's parameter layout;
* ``init_weights(key, cfg)``: fp32 weights from a seed, made by the
  benchmark so that the program and the reference start from the same
  numbers and neither takes them from the other;
* ``examples(seed, mix, cfg, n_rows)``: the rows of a traffic mix, in the
  program's train-batch schema; ``row_keys(batch)``: one key per row;
* ``LOSSES``: the loss and its parts, as the program's step metrics name
  them; ``loss(w, batch, cfg, rq)``: the plain fp32 reference loss of one
  micro-batch, returning ``(loss, parts)`` with the parts in that order;
* ``train_flops_per_token(cfg, mix)``: model FLOPs of one trained token;
* ``tiny(cfg, mix)``: the configuration and mix cut to a width and length
  that the CPU runs in seconds, for the harness's rehearsal in
  ``bench/test_bench_harness.py``.

BERT's reference follows the BERT the program defines
(``repro/models/bert.py``), which departs from the published model in
three ways that PERF.md lists: no biases on the attention projections, no
padding mask in attention, and weight decay on every parameter.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models import api

from bench.reference import exact, mm
from bench.seeds import seed_key, seed_words

LOSSES = ("loss", "mlm_loss", "nsp_loss")

# --- the program's configuration ---------------------------------------


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file; raises where the
    benchmark's weights no longer have the program's parameter layout."""
    if cfg["type_vocab_size"] != 2:
        raise ValueError("the program's BERT has two segment types")
    mcfg = dataclasses.replace(
        get_config(cfg["arch"]), n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        max_position=cfg["max_position"], norm_eps=cfg["norm_eps"])
    mine = jax.eval_shape(lambda k: init_weights(k, cfg), seed_key(0))
    theirs, _ = api.abstract_params(mcfg)
    if jax.tree_util.tree_structure(mine) != \
            jax.tree_util.tree_structure(theirs) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs))):
        raise ValueError("bench/families/bert.py no longer matches the "
                         "program's BERT parameter layout")
    return mcfg


# --- weights -----------------------------------------------------------


def init_weights(key: jax.Array, cfg: dict) -> dict:
    """fp32 weights; call under ``jax.jit`` to make them on the device.
    Every matrix is BERT's truncated normal (two standard deviations,
    ``initializer_range``); LayerNorm scales are 1 and biases 0.  Layer
    parameters are stacked along a leading layer axis, as the program
    scans its blocks."""
    n, d, ff = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    v = cfg["vocab_size"]
    h, dh = cfg["n_heads"], cfg["head_dim"]
    std = cfg["initializer_range"]
    count = iter(range(1000))

    def normal(*shape):
        k = jax.random.fold_in(key, next(count))
        return std * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                 jnp.float32)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), jnp.float32),
                "bias": jnp.zeros(lead + (d,), jnp.float32)}

    return {
        "embed": {"tok": normal(v, d), "pos": normal(cfg["max_position"], d),
                  "type": normal(cfg["type_vocab_size"], d)},
        "embed_norm": norm(),
        "blocks": {
            "attn": {"wq": normal(n, d, h, dh), "wk": normal(n, d, h, dh),
                     "wv": normal(n, d, h, dh), "wo": normal(n, h, dh, d)},
            "attn_norm": norm(n),
            "mlp": {"wi": normal(n, d, ff), "bi": jnp.zeros((n, ff)),
                    "wo": normal(n, ff, d), "bo": jnp.zeros((n, d))},
            "mlp_norm": norm(n),
        },
        "mlm_transform": {"w": normal(d, d), "b": jnp.zeros((d,))},
        "mlm_norm": norm(),
        "mlm_bias": jnp.zeros((v,)),
        "pooler": {"w": normal(d, d), "b": jnp.zeros((d,))},
        "nsp": {"w": normal(d, 2), "b": jnp.zeros((2,))},
    }


# --- traffic -----------------------------------------------------------

PAD, CLS, SEP, MASK = 0, 2, 3, 4
FIRST_WORD = 5          # ids below are the special tokens
IGNORE = -100
ROW_FIELDS = ("tokens", "type_ids", "mlm_positions", "mlm_labels",
              "nsp_labels")


def examples(seed: int, mix: dict, cfg: dict, n_rows: int) -> dict:
    """BERT pre-training examples drawn from a traffic mix and a seed.

    One generator for every BERT mix file under ``bench/traffic/``.  It
    builds the examples the way BERT's data creation does
    (arXiv:1810.04805, section A.1): ``[CLS] A [SEP] B [SEP]`` filled to the
    sequence length, except that a share ``short_seq_prob`` of the examples
    aims at a shorter random length; B follows A or is random text with
    probability ``random_next_prob`` (the NSP label); ``mask_prob`` of the
    tokens, at most ``n_predictions``, are chosen for the MLM loss and
    replaced 80% by [MASK], 10% by a random token and 10% kept.  Token ids
    follow a Zipf law over the vocabulary.

    The result has the train-batch schema of ``repro.models.api``: tokens,
    type_ids, mlm_positions, mlm_labels (-100 where unused), nsp_labels.
    """
    vocab_size = cfg["vocab_size"]
    rng = np.random.default_rng(seed_words(seed) + [0xDA7A])
    s, p_max = mix["seq_len"], mix["n_predictions"]
    body = s - 3
    ranks = np.arange(1, vocab_size - FIRST_WORD + 1, dtype=np.float64)
    zipf = ranks ** -float(mix["zipf_exponent"])
    zipf /= zipf.sum()

    short = rng.random(n_rows) < mix["short_seq_prob"]
    length = np.where(short, rng.integers(2, body + 1, n_rows), body)
    a_len = 1 + (rng.random(n_rows) * (length - 1)).astype(np.int64)
    words = FIRST_WORD + rng.choice(len(zipf), size=(n_rows, body), p=zipf)

    col = np.arange(s)[None, :]
    a_end = 1 + a_len[:, None]                 # index of the first [SEP]
    end = 2 + length[:, None]                  # index of the last [SEP]
    tokens = np.full((n_rows, s), PAD, np.int64)
    in_a = (col >= 1) & (col < a_end)
    in_b = (col > a_end) & (col < end)
    # word j of the row fills the j-th A/B slot
    slot = np.where(col < a_end, col - 1, col - 2)
    slot = np.clip(slot, 0, body - 1)
    text = np.take_along_axis(words, slot, axis=1)
    tokens = np.where(in_a | in_b, text, tokens)
    tokens[:, 0] = CLS
    tokens[col == a_end] = SEP
    tokens[col == end] = SEP
    type_ids = ((col > a_end) & (col <= end)).astype(np.int64)
    nsp = (rng.random(n_rows) < mix["random_next_prob"]).astype(np.int64)

    # MLM: choose round(mask_prob * words) word positions, at most p_max
    n_mask = np.minimum(p_max, np.maximum(
        1, np.rint(length * mix["mask_prob"]).astype(np.int64)))
    keys = np.where(in_a | in_b, rng.random((n_rows, s)), np.inf)
    order = np.argsort(keys, axis=1)[:, :p_max]
    used = np.arange(p_max)[None, :] < n_mask[:, None]
    positions = np.sort(np.where(used, order, s), axis=1)
    used = positions < s
    positions = np.where(used, positions, 0)
    labels = np.where(used, np.take_along_axis(tokens, positions, 1), IGNORE)
    r = rng.random((n_rows, p_max))
    random_word = FIRST_WORD + rng.integers(0, vocab_size - FIRST_WORD,
                                            (n_rows, p_max))
    new = np.where(r < 0.8, MASK, np.where(r < 0.9, random_word, labels))
    rows = np.repeat(np.arange(n_rows)[:, None], p_max, 1)
    tokens[rows[used], positions[used]] = new[used]
    return {"tokens": tokens.astype(np.int32),
            "type_ids": type_ids.astype(np.int32),
            "mlm_positions": positions.astype(np.int32),
            "mlm_labels": labels.astype(np.int32),
            "nsp_labels": nsp.astype(np.int32)}


def row_keys(batch: dict) -> list:
    """One bytes key per row, for finding a row among the examples."""
    n = len(batch["nsp_labels"])
    parts = [np.ascontiguousarray(batch[k]).reshape(n, -1)
             for k in ROW_FIELDS]
    flat = np.concatenate(parts, axis=1).astype(np.int32)
    return [r.tobytes() for r in flat]


# --- the plain reference loss --------------------------------------------


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss(w, batch, cfg, rq=exact):
    """MLM + NSP loss of one micro-batch, and its two parts.  ``rq`` rounds
    every value that the program holds in its compute precision: matmul
    operands and outputs, the residual stream, LayerNorm and activation
    outputs."""
    eps = cfg["norm_eps"]
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = rq(rq(w["embed"]["tok"][tokens] + w["embed"]["pos"][:s][None])
           + w["embed"]["type"][batch["type_ids"]])
    x = rq(_layer_norm(x, w["embed_norm"], eps))

    def layer(x, p):
        a = p["attn"]
        q = rq(mm("bsd,dhk->bshk", x, a["wq"], rq))
        k = rq(mm("bsd,dhk->bshk", x, a["wk"], rq))
        v = rq(mm("bsd,dhk->bshk", x, a["wv"], rq))
        scores = mm("bqhk,bshk->bhqs", q, k, rq) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(scores, axis=-1)
        o = rq(mm("bhqs,bshk->bqhk", probs, v, rq))
        y = rq(mm("bqhk,hkd->bqd", o, a["wo"], rq))
        x = rq(_layer_norm(rq(x + y), p["attn_norm"], eps))
        m = p["mlp"]
        hid = rq(_gelu(rq(mm("bsd,df->bsf", x, m["wi"], rq) + m["bi"])))
        y = rq(mm("bsf,fd->bsd", hid, m["wo"], rq) + m["bo"])
        x = rq(_layer_norm(rq(x + y), p["mlp_norm"], eps))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["blocks"])
    pooled = rq(jnp.tanh(mm("bd,de->be", x[:, 0], w["pooler"]["w"], rq)
                         + w["pooler"]["b"]))
    h = jnp.take_along_axis(x, batch["mlm_positions"][..., None], axis=1)
    h = rq(_gelu(rq(mm("bpd,de->bpe", h, w["mlm_transform"]["w"], rq)
                    + w["mlm_transform"]["b"])))
    h = rq(_layer_norm(h, w["mlm_norm"], eps))
    logits = rq(mm("bpd,vd->bpv", h, w["embed"]["tok"], rq) + w["mlm_bias"])
    labels = batch["mlm_labels"]
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               -1)[..., 0]
    mlm = jnp.sum(nll * valid) / jnp.maximum(valid.sum(), 1)
    nsp_logits = rq(mm("bd,dc->bc", pooled, w["nsp"]["w"], rq)
                    + w["nsp"]["b"])
    nsp_logp = jax.nn.log_softmax(nsp_logits, -1)
    nsp = -jnp.mean(jnp.take_along_axis(
        nsp_logp, batch["nsp_labels"][:, None], -1))
    return mlm + nsp, jnp.stack([mlm, nsp])


# --- model FLOPs -------------------------------------------------------


def forward_flops_per_token(cfg: dict, mix: dict) -> float:
    """Model FLOPs of one token's forward pass at the mix's sequence length
    S, computed from the shapes:

    * per layer: Q, K, V and output projections (4 * 2 * d * h * dh,
      written as d x (h*dh) matmuls), attention scores and
      attention-times-values over the whole sequence (2 * 2 * S * h * dh),
      the FFN (2 * 2 * d * d_ff);
    * the MLM head over the predicted positions only, P of S per sequence:
      (P / S) * (2 * d * d + 2 * d * V);
    * the pooler and NSP head once per sequence: (2 * d * d + 2 * d * 2) / S.

    Embedding lookups, norms, activations, softmax and the optimizer are
    not matmuls and are left out, as model-FLOPs utilisation counts them.
    """
    seq_len, n_predictions = mix["seq_len"], mix["n_predictions"]
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hd = cfg["n_heads"] * cfg["head_dim"]
    layer = 4 * 2 * d * hd + 2 * 2 * seq_len * hd + 2 * 2 * d * ff
    mlm = n_predictions / seq_len * (2 * d * d + 2 * d * v)
    heads = (2 * d * d + 2 * d * 2) / seq_len
    return cfg["n_layers"] * layer + mlm + heads


def train_flops_per_token(cfg: dict, mix: dict) -> float:
    """3x the forward pass's matmul FLOPs (forward, then the gradients with
    respect to activations and to weights); recomputed operations
    (rematerialisation) do not count."""
    return 3.0 * forward_flops_per_token(cfg, mix)


# --- the CPU rehearsal -------------------------------------------------


def tiny(cfg: dict, mix: dict) -> tuple:
    """``(cfg, mix)`` at two layers, width 64 and 32 tokens a row, with
    eight rows per chip and step and 64 per chip in the shard set."""
    return (dict(cfg, n_layers=2, d_model=64, n_heads=2, head_dim=32,
                 d_ff=128, vocab_size=512, max_position=128),
            dict(mix, seq_len=32, n_predictions=5, batch_per_chip=8,
                 rows_per_chip=64))
