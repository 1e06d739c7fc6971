"""Model FLOPs of one BERT pre-training token, computed from the shapes.

Training counts 3x the forward pass's matmul FLOPs (forward, then the
gradients with respect to activations and to weights); recomputed
operations (rematerialisation) do not count.  The forward pass of one
token at sequence length S:

* per layer: Q, K, V and output projections (4 * 2 * d * h * dh, written
  as d x (h*dh) matmuls), attention scores and attention-times-values over
  the whole sequence (2 * 2 * S * h * dh), the FFN (2 * 2 * d * d_ff);
* the MLM head over the predicted positions only, P of S per sequence:
  (P / S) * (2 * d * d + 2 * d * V);
* the pooler and NSP head once per sequence: (2 * d * d + 2 * d * 2) / S.

Embedding lookups, norms, activations, softmax and the optimizer are not
matmuls and are left out, as model-FLOPs utilisation counts them.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def forward_flops_per_token(cfg: dict, seq_len: int,
                            n_predictions: int) -> float:
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hd = cfg["n_heads"] * cfg["head_dim"]
    layer = 4 * 2 * d * hd + 2 * 2 * seq_len * hd + 2 * 2 * d * ff
    mlm = n_predictions / seq_len * (2 * d * d + 2 * d * v)
    heads = (2 * d * d + 2 * d * 2) / seq_len
    return cfg["n_layers"] * layer + mlm + heads


def train_flops_per_token(cfg: dict, seq_len: int,
                          n_predictions: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len, n_predictions)


def chip_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]
