"""A training cell: the program's BERT pre-training path, driven as a job.

The composition is the one ``examples/pretrain_bert.py:train_phases``
builds, taken apart so that it runs from ``--seed``: examples from the
benchmark's generator written as the program's shards (``write_shards``)
and read back by its ``ShardedLoader``; the pure-DP psum step of
``make_train_step_dp`` with bf16 AMP, LAMB and gradient accumulation; the
state placed by ``dp_state_shardings``; every step driven by
``train_loop``.  Checkpointing is off.

Set-up builds one step and one state and drives them through their first
steps by ``train_loop`` on the loader's rows; the window then continues
the same objects.  The first ``reference.STEPS`` steps are the ones the
plain reference follows to decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import shutil
import time
from pathlib import Path

import jax
import numpy as np
from jax import monitoring
from jax.profiler import TraceAnnotation
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.data.pipeline import ShardedLoader, write_shards
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train.train_step import (dp_state_shardings, init_train_state,
                                    make_train_step_dp)
from repro.train.trainer import train_loop

from bench import reference
from bench.traffic import bert_examples, row_keys
from bench.weights import init_weights, seed_key

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TIMING_STEPS = 5        # warm-up steps after the reference's, timed
MIN_WINDOW_STEPS = 10
N_SHARDS = 4            # as train_phases writes them
LOSSES = ("loss", "mlm_loss", "nsp_loss")   # what the reference compares


class CompileClock:
    """Seconds spent getting programs ready, and backend compiles counted."""

    def __init__(self):
        self.seconds, self.compiles = 0.0, 0

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1


class Probe:
    """The step as ``train_loop`` calls it: a timestamp at each entry, a
    ``step.call`` span around the call, and each step's losses and skip
    flag kept on the device."""

    def __init__(self, step):
        self.step = step
        self.entries, self.losses, self.skipped = [], [], []

    def __call__(self, state, batch):
        self.entries.append(time.perf_counter())
        with TraceAnnotation("step.call"):
            state, metrics = self.step(state, batch)
        self.losses.append([metrics[k] for k in LOSSES])
        self.skipped.append(metrics["skipped"])
        return state, metrics

    def reset(self):
        self.entries, self.losses, self.skipped = [], [], []


class Feed:
    """The loader as ``train_loop`` reads it, under a ``data.next`` span;
    keeps the batches it hands out while ``record`` is a list."""

    def __init__(self, loader):
        self.loader, self.record = loader, None

    def __iter__(self):
        return self

    def __next__(self):
        with TraceAnnotation("data.next"):
            batch = next(self.loader)
        if self.record is not None:
            self.record.append(batch)
        return batch


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    if cfg["type_vocab_size"] != 2:
        raise ValueError("the program's BERT has two segment types")
    return dataclasses.replace(
        get_config(cfg["arch"]), n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        max_position=cfg["max_position"], norm_eps=cfg["norm_eps"])


def check_layout(cfg: dict, mcfg) -> None:
    """The benchmark's weights must have the program's parameter layout."""
    mine = jax.eval_shape(lambda k: init_weights(k, cfg), seed_key(0))
    theirs, _ = api.abstract_params(mcfg)
    if jax.tree_util.tree_structure(mine) != \
            jax.tree_util.tree_structure(theirs) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs))):
        raise ValueError("bench/weights.py no longer matches the program's "
                         "BERT parameter layout")


class TrainCell:
    """The compiled step of one (configuration, traffic mix) pair on its
    mesh; ``start(seed)`` gives it a state and a loader."""

    state = key = None

    def __init__(self, cfg: dict, mix: dict, devices, workdir: Path):
        self.cfg, self.mix, self.workdir = cfg, mix, Path(workdir)
        dp = mix["data_parallel"]
        if len(devices) < dp:
            raise ValueError(f"{mix['name']} needs {dp} devices")
        self.devices = list(devices[:dp])
        self.mesh = make_mesh((dp, 1), ("data", "model"),
                              devices=self.devices)
        self.global_batch = dp * mix["batch_per_chip"]
        self.tokens_per_step = self.global_batch * mix["seq_len"]
        self.n_micro = dp * mix["accum"]
        opt = cfg["optimizer"]
        self.tcfg = TrainConfig(
            precision=cfg["precision"], accum_steps=mix["accum"],
            collective_strategy="psum", optimizer=opt["name"],
            learning_rate=mix["learning_rate"],
            warmup_steps=mix["warmup_steps"], total_steps=mix["total_steps"],
            weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"])
        self.mcfg = model_config(cfg)
        check_layout(cfg, self.mcfg)
        shape = InputShape(mix["name"], mix["seq_len"], self.global_batch,
                           "train")
        self.step, _ = make_train_step_dp(self.mcfg, self.tcfg, self.mesh,
                                          shape)
        policy = make_policy(cfg["precision"])

        def make_state(key):
            return init_train_state(init_weights(key, cfg), policy,
                                    self.tcfg, world=dp)

        placement = dp_state_shardings(
            jax.eval_shape(make_state, seed_key(0)), self.mesh)
        self.make_state = jax.jit(make_state, out_shardings=placement)
        self._first_grad = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda a: a / (1.0 - opt["b1"]), m))
        self._change_norms = jax.jit(
            lambda w, key: reference.leaf_change_norms(
                w, init_weights(key, cfg)))
        self._reference_weights = jax.jit(
            lambda key: init_weights(key, cfg),
            out_shardings=SingleDeviceSharding(self.devices[0]))

    def examples(self, seed: int) -> dict:
        return bert_examples(seed, self.mix, self.cfg["vocab_size"],
                             self.mix["rows_per_chip"] * len(self.devices))

    def loader(self, seed: int, rows: dict) -> ShardedLoader:
        """The program's shards of ``rows``, read back by its loader."""
        shard_dir = self.workdir / "shards"
        shutil.rmtree(shard_dir, ignore_errors=True)
        write_shards(rows, str(shard_dir), N_SHARDS)
        try:
            return ShardedLoader(str(shard_dir), worker=0, n_workers=1,
                                 batch=self.global_batch,
                                 seed=int(seed) % (1 << 64))
        finally:
            shutil.rmtree(shard_dir, ignore_errors=True)

    def drive(self, probe, feed, steps: int) -> None:
        """``steps`` more steps of ``self.state`` through the program's
        train_loop.  The state is handed over, not kept here meanwhile, so
        that only the loop holds it, as a training job's loop does."""
        self.state, _ = train_loop(probe, self._take(), feed,
                                   total_steps=steps, log_every=steps)

    def _take(self):
        state, self.state = self.state, None
        return state

    def first_steps(self, probe, feed) -> tuple:
        """Steps 1..STEPS, with what the reference compares: each step's
        loss with its MLM and NSP parts, the first gradient as LAMB got it
        (its first moment over 1 - b1, kept on the host) with its per-leaf
        max-norms, and 2-norms of the weights' change after STEPS steps.
        Returns (readings, the steps' batches)."""
        feed.record = []
        probe.reset()
        self.drive(probe, feed, 1)
        grad = self._first_grad(self.state.opt.m)
        grad_norms = np.asarray(reference.leaf_max_norms(grad))
        grad = jax.device_get(grad)
        self.drive(probe, feed, reference.STEPS - 1)
        change = np.asarray(self._change_norms(self.state.opt.master,
                                               self.key))
        losses = [[float(x) for x in step] for step in probe.losses]
        batches, feed.record = feed.record, None
        return {"losses": losses, "grad": grad, "grad_norms": grad_norms,
                "change_norms": change}, batches

    def start(self, seed: int):
        """A fresh state for ``seed`` in ``self.state``, and its loader;
        returns (feed, rows, seconds to make the weights and the data)."""
        self.state = None
        self.key = seed_key(seed)
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self.make_state(self.key))
        t1 = time.perf_counter()
        rows = self.examples(seed)
        feed = Feed(self.loader(seed, rows))
        t2 = time.perf_counter()
        return feed, rows, {"weights": t1 - t0, "data": t2 - t1}

    def reference_readings(self, rows: dict, batches: list,
                           rq=reference.exact, row_share: float = 1.0
                           ) -> tuple:
        """The reference over the same rows, taken from the benchmark's own
        examples; the rows of a batch that are not among them are counted.
        ``row_share`` < 1 keeps only that leading share of each batch (a
        fault put in the program's place)."""
        index = {k: i for i, k in enumerate(row_keys(rows))}
        picked, missing = [], 0
        for batch in batches:
            ids = [index.get(k, -1) for k in row_keys(batch)]
            missing += sum(i < 0 for i in ids)
            ids = np.asarray([max(i, 0) for i in ids])
            n = int(len(ids) * row_share)
            picked.append({k: v[ids[:n]] for k, v in rows.items()})
        n_micro = max(1, int(self.n_micro * row_share))
        workers = len(self.devices) if row_share == 1.0 else 1
        w0 = self._reference_weights(self.key)
        out = reference.readings(w0, picked, self.cfg, self.mix, n_micro,
                                 workers=workers, rq=rq,
                                 device=self.devices[0])
        return out, missing


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
