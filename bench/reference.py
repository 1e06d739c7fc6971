"""Plain float32 reference of the BERT pre-training step, and its readings.

Written from the model's description with ``jax.numpy`` alone; it imports
nothing of the program.  Every matmul runs at the highest precision.  It
follows the BERT the program defines (``repro/models/bert.py``), which
departs from the published model in three ways that PERF.md lists: no
biases on the attention projections, no padding mask in attention, and
weight decay on every parameter.

The step it follows: the global batch split into micro-batches of
consecutive rows (``n_micro`` = data-parallel workers x accumulation), the
loss and the gradients averaged over them, the gradients clipped by their
global norm, then LAMB (You et al., arXiv:1904.00962) with a trust ratio
per stored leaf, under linear warm-up and linear decay of the learning
rate.  A leaf of the layers' parameters stacks all layers, so its trust
ratio is taken over all of them at once: the program's grouping, where
the published LAMB takes one per layer (PERF.md lists it).

``rq`` rounds what the program computes in bf16 (matmul operands and
results, the residual stream, norm and activation outputs) to a lower
precision; the benchmark's control uses ``fp8_round`` there, the
precision below the configuration's bf16, on the values and, in the
backward pass, on their gradients.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STEPS = 3               # the program's first steps that the reference follows


def exact(x):
    return x


def _e4m3(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


@jax.custom_vjp
def fp8_round(x):
    """x rounded to float8 e4m3 under a per-tensor scale; in the backward
    pass its gradient is rounded the same way."""
    return _e4m3(x)


fp8_round.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (_e4m3(g),))


def _mm(eq, a, b, rq):
    return jnp.einsum(eq, rq(a), rq(b), precision=HIGHEST)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def bert_loss(w, batch, cfg, rq=exact):
    """MLM + NSP loss of one micro-batch, and its two parts.  ``rq`` rounds
    every value that
    the program holds in its compute precision: matmul operands and
    outputs, the residual stream, LayerNorm and activation outputs."""
    eps = cfg["norm_eps"]
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = rq(rq(w["embed"]["tok"][tokens] + w["embed"]["pos"][:s][None])
           + w["embed"]["type"][batch["type_ids"]])
    x = rq(_layer_norm(x, w["embed_norm"], eps))

    def layer(x, p):
        a = p["attn"]
        q = rq(_mm("bsd,dhk->bshk", x, a["wq"], rq))
        k = rq(_mm("bsd,dhk->bshk", x, a["wk"], rq))
        v = rq(_mm("bsd,dhk->bshk", x, a["wv"], rq))
        scores = _mm("bqhk,bshk->bhqs", q, k, rq) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(scores, axis=-1)
        o = rq(_mm("bhqs,bshk->bqhk", probs, v, rq))
        y = rq(_mm("bqhk,hkd->bqd", o, a["wo"], rq))
        x = rq(_layer_norm(rq(x + y), p["attn_norm"], eps))
        m = p["mlp"]
        hid = rq(_gelu(rq(_mm("bsd,df->bsf", x, m["wi"], rq) + m["bi"])))
        y = rq(_mm("bsf,fd->bsd", hid, m["wo"], rq) + m["bo"])
        x = rq(_layer_norm(rq(x + y), p["mlp_norm"], eps))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["blocks"])
    pooled = rq(jnp.tanh(_mm("bd,de->be", x[:, 0], w["pooler"]["w"], rq)
                         + w["pooler"]["b"]))
    h = jnp.take_along_axis(x, batch["mlm_positions"][..., None], axis=1)
    h = rq(_gelu(rq(_mm("bpd,de->bpe", h, w["mlm_transform"]["w"], rq)
                    + w["mlm_transform"]["b"])))
    h = rq(_layer_norm(h, w["mlm_norm"], eps))
    logits = rq(_mm("bpd,vd->bpv", h, w["embed"]["tok"], rq) + w["mlm_bias"])
    labels = batch["mlm_labels"]
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               -1)[..., 0]
    mlm = jnp.sum(nll * valid) / jnp.maximum(valid.sum(), 1)
    nsp_logits = rq(_mm("bd,dc->bc", pooled, w["nsp"]["w"], rq)
                    + w["nsp"]["b"])
    nsp_logp = jax.nn.log_softmax(nsp_logits, -1)
    nsp = -jnp.mean(jnp.take_along_axis(
        nsp_logp, batch["nsp_labels"][:, None], -1))
    return mlm + nsp, jnp.stack([mlm, nsp])


@partial(jax.jit, static_argnames=("cfg_items", "n_micro", "rq"))
def _loss_and_grad(w, batch, *, cfg_items, n_micro, rq):
    cfg = dict(cfg_items)
    micro = jax.tree_util.tree_map(
        lambda a: a.reshape((n_micro, -1) + a.shape[1:]), batch)
    grad_fn = jax.value_and_grad(lambda w, mb: bert_loss(w, mb, cfg, rq),
                                 has_aux=True)

    def body(carry, mb):
        (loss, parts), g = grad_fn(w, mb)
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], g)), parts

    zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, w))
    (loss, g), parts = jax.lax.scan(body, zero, micro)
    return (loss / n_micro, jax.tree_util.tree_map(lambda a: a / n_micro, g),
            parts)


def learning_rate(step: int, sched: dict) -> float:
    base, warm, total = (sched["learning_rate"], sched["warmup_steps"],
                         sched["total_steps"])
    if step < warm:
        return base * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (1.0 - frac)


@partial(jax.jit, static_argnames=("opt_items",))
def _clip_lamb(w, g, m, v, step, lr, *, opt_items):
    opt = dict(opt_items)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree_util.tree_map(lambda a: a * clip, g)

    def leaf(w, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        r = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps) \
            + wd * w
        wn, rn = jnp.sqrt(jnp.sum(w * w)), jnp.sqrt(jnp.sum(r * r))
        trust = jnp.where((wn > 0) & (rn > 0), wn / jnp.where(rn > 0, rn, 1),
                          1.0)
        return w - lr * trust * r, m, v

    out = jax.tree_util.tree_map(leaf, w, g, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), g


@jax.jit
def leaf_norms(tree):
    """Each leaf's 2-norm."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


@jax.jit
def leaf_max_norms(tree):
    """Each leaf's max-norm: its largest magnitude."""
    return jnp.stack([jnp.max(jnp.abs(a.astype(jnp.float32)))
                      for a in jax.tree_util.tree_leaves(tree)])


@jax.jit
def leaf_change_norms(new, old):
    """Each leaf's 2-norm of ``new - old``."""
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, new, old))


def readings(w0, batches, cfg: dict, sched: dict, n_micro: int,
             workers: int = 1, rq=exact, device=None) -> dict:
    """From ``w0`` over ``batches`` (host arrays), for each of the first
    ``STEPS`` steps: the loss and, as the program reports them, its MLM and
    NSP parts on each worker's last micro-batch, averaged over the
    ``workers``; the first clipped gradient (``grad``, on the device) and
    its per-leaf max-norms; per-leaf 2-norms of the weights' change after
    ``STEPS`` steps."""
    device = device or jax.devices()[0]
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    opt_items = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                             if isinstance(v, (int, float))))
    w0 = jax.device_put(w0, device)
    w, m, v = w0, *(jax.tree_util.tree_map(jnp.zeros_like, w0),) * 2
    losses, grad = [], None
    for step in range(1, len(batches) + 1):
        batch = jax.device_put(batches[step - 1], device)
        loss, g, parts = _loss_and_grad(w, batch, cfg_items=cfg_items,
                                        n_micro=n_micro, rq=rq)
        per = n_micro // workers
        last = np.asarray(parts)[per - 1::per].mean(axis=0)
        losses.append([float(loss), float(last[0]), float(last[1])])
        w, m, v, g = _clip_lamb(w, g, m, v, jnp.float32(step),
                                jnp.float32(learning_rate(step, sched)),
                                opt_items=opt_items)
        if step == 1:
            grad = g
        del g
    change = np.asarray(leaf_change_norms(w, w0))
    return {"losses": losses, "grad": grad,
            "grad_norms": np.asarray(leaf_max_norms(grad)),
            "change_norms": change}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    loss_gap: the root mean square, over the steps, of the gaps to the
    reference of the loss and of its MLM and NSP parts, in nats.
    grad_gap, change_gap: the worst leaf's |norm - reference norm|, over
    the larger of that leaf's reference norm and the median leaf's: the
    max-norm of the first gradient, the 2-norm of the change after the
    steps.  Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of change_gap.
    grad_err: the median leaf's 2-norm of the first gradient's difference
    from the reference's, over the larger of that leaf's reference 2-norm
    and the median leaf's.  A gap of norms is one draw of the rounding
    noise and swings between seeds; this sums the noise over every element
    and is steady, where the worst leaf is a leaf that the program sums
    over every token in bf16 (PERF.md).
    """
    def scaled(gap, r):
        r = np.asarray(r, np.float64)
        denom = np.maximum(r, np.median(r))
        gap = np.abs(gap) / np.where(denom > 0, denom, 1.0)
        return np.where(np.isfinite(gap), gap, np.inf)

    def worst(gap, r, keep=True):
        return float(np.max(np.where(keep, scaled(gap, r), 0.0)))

    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moves = g_ref >= 1e-3 * np.median(g_ref)
    gaps = np.subtract(prog["losses"], ref["losses"])
    loss_gap = float(np.sqrt(np.mean(np.square(gaps))))
    if not np.all(np.isfinite(gaps)):
        loss_gap = float("inf")
    change = np.asarray(ref["change_norms"], np.float64)
    return {"loss_gap": loss_gap,
            "grad_gap": worst(np.subtract(prog["grad_norms"], g_ref), g_ref),
            "grad_err": float(np.median(scaled(
                leaf_change_norms(prog["grad"], ref["grad"]),
                leaf_norms(ref["grad"])))),
            "change_gap": worst(np.subtract(prog["change_norms"], change),
                                change, moves)}
