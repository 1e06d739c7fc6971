"""Data pipeline (paper §3.1.1, §4.1): tokenizer, masking, NSP, sharding."""
import json
from pathlib import Path

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.data.pipeline import (BertExampleConfig, ShardedLoader,
                                 build_bert_examples, prepare_bert_data,
                                 read_shard, write_shards)
from repro.data.tokenizer import (WordPieceTokenizer, synth_corpus,
                                  train_wordpiece)


@pytest.fixture(scope="module")
def tok():
    docs = synth_corpus(n_docs=50, seed=0)
    return train_wordpiece((s for d in docs for s in d), vocab_size=2048)


def test_tokenizer_covers_corpus(tok):
    docs = synth_corpus(n_docs=10, seed=1)
    unk = 0
    total = 0
    for d in docs:
        for s in d:
            ids = tok.encode(s)
            total += len(ids)
            unk += sum(1 for i in ids if i == tok.unk_id)
    assert total > 0
    assert unk / total < 0.01  # single-char fallback keeps UNK rare


def test_tokenizer_save_load_roundtrip(tok, tmp_path):
    p = tmp_path / "vocab.json"
    tok.save(str(p))
    tok2 = WordPieceTokenizer.load(str(p))
    s = "bake note lulu"
    assert tok.encode(s) == tok2.encode(s)


def test_bert_examples_schema_and_masking(tok):
    docs_text = synth_corpus(n_docs=40, seed=2)
    docs = [[tok.encode(s) for s in d] for d in docs_text]
    cfg = BertExampleConfig(seq_len=64, n_predictions=10)
    ex = build_bert_examples(docs, tok, cfg, seed=0)
    n = len(ex["tokens"])
    assert n > 10
    assert ex["tokens"].shape == (n, 64)
    assert ex["mlm_positions"].shape == (n, 10)
    assert ex["nsp_labels"].shape == (n,)
    # NSP ~50/50
    frac = ex["nsp_labels"].mean()
    assert 0.25 < frac < 0.75
    # masked positions carry real labels; pad slots are -100
    valid = ex["mlm_labels"] >= 0
    assert valid.any(axis=1).all()
    # ~15% of non-special tokens masked (cap at n_predictions)
    toks = ex["tokens"]
    n_masked = (toks == tok.mask_id).sum()
    n_valid = valid.sum()
    assert n_masked >= 0.7 * 0.8 * n_valid  # 80% of masks are [MASK]
    # each mlm_position points at a maskable slot
    rows = np.arange(n)[:, None]
    pointed = toks[rows, ex["mlm_positions"]]
    assert (pointed[valid] != tok.cls_id).all()


@settings(max_examples=10, deadline=None)
@given(n_shards=st.sampled_from([1, 2, 4, 8]))
def test_shards_exact_cover(tmp_path_factory, n_shards):
    tmp = tmp_path_factory.mktemp(f"shards{n_shards}")
    ex = {"tokens": np.arange(400, dtype=np.int32).reshape(100, 4),
          "nsp_labels": np.arange(100, dtype=np.int32)}
    paths = write_shards(ex, str(tmp), n_shards)
    assert len(paths) == n_shards
    got = np.concatenate([read_shard(p)["nsp_labels"] for p in paths])
    np.testing.assert_array_equal(np.sort(got), np.arange(100))


def test_sharded_loader_reads_only_own_shard(tmp_path):
    ex = {"tokens": np.arange(800, dtype=np.int32).reshape(200, 4),
          "nsp_labels": np.repeat(np.arange(8), 25).astype(np.int32)}
    write_shards(ex, str(tmp_path), 8)
    loaders = [ShardedLoader(str(tmp_path), w, 4, batch=8) for w in range(4)]
    seen = [set() for _ in range(4)]
    for w, ld in enumerate(loaders):
        it = iter(ld)
        for _ in range(6):
            b = next(it)
            assert b["tokens"].shape == (8, 4)
            seen[w].update(b["tokens"][:, 0].tolist())
    # workers see disjoint example sets (their own shards)
    for i in range(4):
        for j in range(i + 1, 4):
            assert not (seen[i] & seen[j])


def test_prepare_bert_data_end_to_end(tmp_path):
    tok, index = prepare_bert_data(str(tmp_path), seq_len=64, n_docs=30,
                                   vocab_size=1024, n_shards=4)
    assert index.exists()
    meta = json.loads(index.read_text())
    assert meta["n_shards"] == 4
    ld = ShardedLoader(str(tmp_path), 0, 2, batch=4)
    b = next(iter(ld))
    assert b["tokens"].shape == (4, 64)


@pytest.mark.parametrize("phase_idx,seq,preds", [(0, 128, 20), (1, 512, 80)])
def test_bert_phase_batches_match_step_struct(tmp_path, phase_idx, seq,
                                              preds):
    """Paper Table 6: 20 predictions at seq 128, 80 at 512 -- the loader's
    batches and the train step's batch struct agree on that count."""
    from repro.configs import get_config
    from repro.models import api
    from repro.train.phases import bert_phases

    phase = bert_phases(1, 1, global_batch_p1=4, global_batch_p2=4)[phase_idx]
    assert (phase.seq_len, phase.n_predictions) == (seq, preds)
    prepare_bert_data(str(tmp_path), seq_len=seq,
                      n_predictions=phase.n_predictions, n_docs=30,
                      vocab_size=1024, n_shards=2)
    got = next(iter(ShardedLoader(str(tmp_path), 0, 1, batch=4)))
    want = api.train_batch_struct(get_config("bert-large"), phase.shape)
    assert {k: v.shape for k, v in got.items() if k in want} == \
        {k: v.shape for k, v in want.items()}


def test_packed_lm_examples(tok):
    from repro.data.pipeline import build_lm_examples
    docs_text = synth_corpus(n_docs=30, seed=3)
    docs = [[tok.encode(s) for s in d] for d in docs_text]
    ex = build_lm_examples(docs, tok, seq_len=64)
    assert ex["tokens"].shape[1] == 65
    assert ex["tokens"].shape[0] > 5
    # exact-cover of the stream: all ids valid, separators present
    assert (ex["tokens"] >= 0).all() and (ex["tokens"] < len(tok)).all()
    assert (ex["tokens"] == tok.sep_id).sum() >= 25  # ~1 per document


def test_prepare_lm_data_end_to_end(tmp_path):
    from repro.data.pipeline import ShardedLoader, prepare_lm_data
    tok, index = prepare_lm_data(str(tmp_path), seq_len=32, n_docs=40,
                                 vocab_size=1024, n_shards=4)
    ld = ShardedLoader(str(tmp_path), 0, 2, batch=4)
    b = next(iter(ld))
    assert b["tokens"].shape == (4, 33)
