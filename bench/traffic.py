"""BERT pre-training examples drawn from a traffic mix and a seed.

One generator for every mix file under ``bench/traffic/``.  It builds the
examples the way BERT's data creation does (arXiv:1810.04805, section A.1):
``[CLS] A [SEP] B [SEP]`` filled to the sequence length, except that a
share ``short_seq_prob`` of the examples aims at a shorter random length;
B follows A or is random text with probability ``random_next_prob`` (the
NSP label); ``mask_prob`` of the tokens, at most ``n_predictions``, are
chosen for the MLM loss and replaced 80% by [MASK], 10% by a random token
and 10% kept.  Token ids follow a Zipf law over the vocabulary.

The result has the train-batch schema of ``repro.models.api``: tokens,
type_ids, mlm_positions, mlm_labels (-100 where unused), nsp_labels.
"""
from __future__ import annotations

import numpy as np

PAD, CLS, SEP, MASK = 0, 2, 3, 4
FIRST_WORD = 5          # ids below are the special tokens
IGNORE = -100


def seed_words(seed: int) -> list:
    """Non-negative 32-bit words of any whole ``seed``, for numpy's RNG."""
    s = int(seed) % (1 << 64)
    return [s >> 32, s & 0xFFFFFFFF]


def bert_examples(seed: int, mix: dict, vocab_size: int, n_rows: int
                  ) -> dict:
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
             for k in ("tokens", "type_ids", "mlm_positions", "mlm_labels",
                       "nsp_labels")]
    flat = np.concatenate(parts, axis=1).astype(np.int32)
    return [r.tobytes() for r in flat]
