"""The family split, checked on the CPU.

The BERT family's examples, weights, FLOPs and reference loss are pinned
to values read before BERT moved into ``bench/families/bert.py``, at a
tiny width and a fixed seed.  A second family enters as new files alone:
``resolve`` finds it in a copy of the tree where no file that was there
has changed, and an unknown or incomplete family is refused by name.
``toy_tree`` makes such a copy with a family that trains, whose cell the
harness rehearsal in ``bench/test_bench_harness.py`` runs.
"""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench.seeds import seed_key

ROOT = Path(__file__).resolve().parent.parent
SEED = 2**31 + 5
TINY = dict(n_layers=2, d_model=64, n_heads=2, head_dim=32, d_ff=128,
            vocab_size=512, max_position=128)
WEIGHT_SHAPES = [
    ("['blocks']['attn']['wk']", (2, 64, 2, 32)),
    ("['blocks']['attn']['wo']", (2, 2, 32, 64)),
    ("['blocks']['attn']['wq']", (2, 64, 2, 32)),
    ("['blocks']['attn']['wv']", (2, 64, 2, 32)),
    ("['blocks']['attn_norm']['bias']", (2, 64)),
    ("['blocks']['attn_norm']['scale']", (2, 64)),
    ("['blocks']['mlp']['bi']", (2, 128)),
    ("['blocks']['mlp']['bo']", (2, 64)),
    ("['blocks']['mlp']['wi']", (2, 64, 128)),
    ("['blocks']['mlp']['wo']", (2, 128, 64)),
    ("['blocks']['mlp_norm']['bias']", (2, 64)),
    ("['blocks']['mlp_norm']['scale']", (2, 64)),
    ("['embed']['pos']", (128, 64)),
    ("['embed']['tok']", (512, 64)),
    ("['embed']['type']", (2, 64)),
    ("['embed_norm']['bias']", (64,)),
    ("['embed_norm']['scale']", (64,)),
    ("['mlm_bias']", (512,)),
    ("['mlm_norm']['bias']", (64,)),
    ("['mlm_norm']['scale']", (64,)),
    ("['mlm_transform']['b']", (64,)),
    ("['mlm_transform']['w']", (64, 64)),
    ("['nsp']['b']", (2,)),
    ("['nsp']['w']", (64, 2)),
    ("['pooler']['b']", (64,)),
    ("['pooler']['w']", (64, 64)),
]


@pytest.fixture(scope="module")
def bert():
    return bench_run.family("bert")


@pytest.fixture(scope="module")
def tiny():
    spec = bench_run.resolve("bert-large.p1-s128")
    spec["config"].update(TINY)
    spec["mix"].update(seq_len=32, n_predictions=5)
    return spec["config"], spec["mix"]


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_examples_are_pinned(bert, tiny):
    cfg, mix = tiny
    rows = bert.examples(SEED, mix, cfg, 16)
    assert {k: (v.shape, str(v.dtype)) for k, v in rows.items()} == {
        "tokens": ((16, 32), "int32"), "type_ids": ((16, 32), "int32"),
        "mlm_positions": ((16, 5), "int32"),
        "mlm_labels": ((16, 5), "int32"), "nsp_labels": ((16,), "int32")}
    h = hashlib.sha256()
    for k in sorted(rows):
        h.update(k.encode())
        h.update(np.ascontiguousarray(rows[k]).tobytes())
    assert h.hexdigest() == \
        "886c25deff414402cca8d00e9f31aceac03d9435722730ffa080e71c5d71dc77"
    assert hashlib.sha256(b"".join(bert.row_keys(rows))).hexdigest() == \
        "5f82b3a4f0e3d4ec8c4cfbb22211fd46a5c3f9346cce78fd23723f5b527a3c84"


def test_weights_are_pinned(bert, tiny):
    cfg, _ = tiny
    w = bert.init_weights(seed_key(SEED), cfg)
    paths = jax.tree_util.tree_flatten_with_path(w)[0]
    assert [(jax.tree_util.keystr(p), a.shape) for p, a in paths] == \
        WEIGHT_SHAPES
    assert _digest(a for _, a in paths) == \
        "6eef9a5f1257a16b5a0d53922c7c112c13fdd9c123af35a2bcb72bee7fa307ce"


@pytest.mark.parametrize("config,seq_len,n_predictions,flops", [
    ("tiny", 32, 5, 477720.0),
    ("bert-large", 128, 20, 1880021472.0),
    ("bert-large", 512, 80, 1993230744.0),
])
def test_flops_per_token_are_pinned(bert, tiny, config, seq_len,
                                    n_predictions, flops):
    cfg = tiny[0] if config == "tiny" else json.loads(
        (ROOT / "bench" / "configs" / f"{config}.json").read_text())
    mix = {"seq_len": seq_len, "n_predictions": n_predictions}
    assert bert.train_flops_per_token(cfg, mix) == flops


def test_reference_loss_is_pinned(bert, tiny):
    cfg, mix = tiny
    rows = bert.examples(SEED, mix, cfg, 16)
    batch = {k: v[:4] for k, v in rows.items()}
    w = bert.init_weights(seed_key(SEED), cfg)
    loss, parts = jax.jit(lambda w, b: bert.loss(w, b, cfg))(w, batch)
    assert len(bert.LOSSES) == 1 + len(parts)
    assert float(loss) == pytest.approx(6.915493011474609, rel=1e-6)
    assert [float(x) for x in parts] == pytest.approx(
        [6.2222900390625, 0.6932027339935303], rel=1e-6)


TOY = '''
"""A family that names everything the harness asks of one."""
LOSSES = ("loss",)


def program_config(cfg):
    return cfg


def init_weights(key, cfg):
    return {}


def examples(seed, mix, cfg, n_rows):
    return {}


def row_keys(batch):
    return []


def loss(w, batch, cfg, rq):
    return 0.0, ()


def train_flops_per_token(cfg, mix):
    return 1.0


def tiny(cfg, mix):
    return cfg, mix
'''

# A family that trains: BERT's model under a name of its own, with a tiny
# width of its own, in a module of its own.
TOY_FAMILY = '''
"""BERT's names, taken from bench/families/bert.py, and one layer at the
CPU rehearsal's width."""
from pathlib import Path

from bench.run import load

_bert = load(Path(__file__).with_name("bert.py"), "toy_bert")
LOSSES = _bert.LOSSES
program_config, init_weights = _bert.program_config, _bert.init_weights
examples, row_keys, loss = _bert.examples, _bert.row_keys, _bert.loss
train_flops_per_token = _bert.train_flops_per_token


def tiny(cfg, mix):
    cfg, mix = _bert.tiny(cfg, mix)
    return dict(cfg, n_layers=1), mix
'''
TOY_CELL = "toy.p1-s128"


def _copy_tree(dst: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def _add_toy_family(root: Path, source: str, config: dict) -> None:
    """A second family entered as new files and additions to
    BENCHMARK.json: its module, configuration, limits and one cell."""
    (root / "bench" / "families" / "toy.py").write_text(source)
    (root / "bench" / "configs" / "toy.json").write_text(
        json.dumps(dict(config, name="toy", family="toy")))
    shutil.copy(root / "bench" / "limits" / "bert-large.p1-s128.json",
                root / "bench" / "limits" / "toy.p1-s128.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "-",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": TOY_CELL, "config": "toy",
                               "traffic": "p1-s128", "chips": 1,
                               "why": "-"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def toy_tree(root: Path) -> Path:
    """A copy of the benchmark under ``root`` with ``TOY_FAMILY`` added as
    new files, on BERT-base's sizes; returns ``root``."""
    _copy_tree(root)
    _add_toy_family(root, TOY_FAMILY, json.loads(
        (ROOT / "bench" / "configs" / "bert-base.json").read_text()))
    return root


def test_a_family_enters_as_new_files(tmp_path):
    _copy_tree(tmp_path)
    before = _files(tmp_path)
    old = json.loads((tmp_path / "BENCHMARK.json").read_text())
    _add_toy_family(tmp_path, TOY, {})
    after = _files(tmp_path)
    assert {k: after[k] for k in before} == before
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert all(new[k][:len(v)] == v if isinstance(v, list) else new[k] == v
               for k, v in old.items())

    spec = bench_run.resolve(TOY_CELL, tmp_path)
    fam = spec["family"]
    assert Path(fam.__file__) == tmp_path / "bench" / "families" / "toy.py"
    assert fam.LOSSES == ("loss",)
    assert spec["mix"]["name"] == "p1-s128"
    assert bench_run.resolve("bert-large.p1-s128", tmp_path)[
        "family"].LOSSES == ("loss", "mlm_loss", "nsp_loss")


def test_an_unknown_family_lists_the_known_ones():
    with pytest.raises(SystemExit, match=r"unknown model family 'nope'; "
                                         r"known: \['bert'\]"):
        bench_run.family("nope")


def test_an_incomplete_family_is_refused(tmp_path):
    _copy_tree(tmp_path)
    _add_toy_family(tmp_path, TOY.replace("def loss(", "def not_loss("), {})
    with pytest.raises(SystemExit, match=r"toy.py lacks \['loss'\]"):
        bench_run.resolve(TOY_CELL, tmp_path)
