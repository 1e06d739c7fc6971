"""Supervised training loop: step function x data stream x checkpoints.

Beyond the plain drive-the-step loop, this is the fault-tolerance layer
the 12-day-commodity-cluster setting demands (and ``train/faults.py``
injects against):

* **Exact resume.**  ``resume=True`` restores the newest *valid*
  checkpoint (corrupt/torn ones are skipped with a warning inside
  ``restore_checkpoint`` -- never a silent restart from step 0; only a
  genuinely empty checkpoint dir starts fresh, with an info log).  The
  manifest's ``extra`` carries the data-loader cursor: if ``batches``
  exposes ``state_dict()``/``load_state_dict()`` (ShardedLoader, LMStream)
  the sample stream continues exactly where the crashed run left it, so a
  resumed loss trajectory is bit-identical to an uninterrupted one.
* **Non-finite supervision.**  Steps reporting a non-finite loss (or the
  AMP ``skipped`` flag from core/amp.py's dynamic loss scale -- this loop
  *observes* that machinery, it does not duplicate it) are counted;
  ``max_consecutive_skips`` bounds how many may occur back-to-back before
  the run aborts with an emergency checkpoint instead of burning days on
  a diverged model.  Counts surface as ``consecutive_skips``/
  ``total_skips`` metrics.
* **Step watchdog.**  An EMA of step duration flags hangs/stragglers:
  steps slower than ``watchdog_factor`` x the EMA log a warning and count
  into the ``slow_steps`` metric.
* **Bounded retry.**  Transient step failures (``TransientStepError``)
  are retried up to ``max_retries`` times with linear backoff before giving
  up.  Any other error -- a compile failure, device OOM or lost device --
  surfaces on its first attempt: retrying it would only recompile and fail
  again.
* **Emergency checkpoint.**  Any exception escaping the loop triggers a
  best-effort ``save_checkpoint`` at the last completed step before
  re-raising (hard crashes -- ``os._exit`` -- by design get nothing;
  that is what the atomic checkpoint + resume path is for).

Profiler spans.  Under ``jax.profiler.trace`` each loop iteration is a
``train.step`` (a ``StepTraceAnnotation`` carrying ``step_num``) holding,
in order, ``train.data`` (``next(batches)``), ``train.dispatch`` (one per
attempt at the step call, with its ``attempt`` number), ``train.sync``
(the non-finite supervision's read of the loss and skip flag: the per-step
wait for the device) and ``train.checkpoint`` (a save).  The spans only
mark work the loop does anyway; with no profiler running each costs one
check.
"""
from __future__ import annotations

import time
from typing import Callable, Iterator, Optional

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.amp import LossScaleState, loss_scale_summary
from repro.train.checkpoint import (load_manifest, restore_checkpoint,
                                    save_checkpoint)
from repro.train.faults import FaultInjector, TransientStepError
from repro.utils import logger


class NonFiniteBudgetError(RuntimeError):
    """Too many consecutive non-finite (skipped) steps: run aborted."""


def _checkpoint_extra(batches, state, *, fingerprint: Optional[str],
                      seed: Optional[int]) -> dict:
    extra: dict = {"wall_time": time.time()}
    if fingerprint is not None:
        extra["fingerprint"] = fingerprint
    if seed is not None:
        extra["seed"] = seed
    if hasattr(batches, "state_dict"):
        extra["data_state"] = batches.state_dict()
    ls = getattr(state, "loss_scale", None)
    if isinstance(ls, LossScaleState):
        extra["loss_scale"] = loss_scale_summary(ls)
    return extra


def _resume(state, batches, ckpt_dir: str, fingerprint: Optional[str]):
    """Restore (state, start_step), reloading the data cursor if possible."""
    try:
        state, start = restore_checkpoint(ckpt_dir, state)
    except FileNotFoundError:
        logger.info("no checkpoint in %s: starting fresh from step 0",
                    ckpt_dir)
        return state, 0
    logger.info("resumed from checkpoint step %d in %s", start, ckpt_dir)
    manifest = load_manifest(ckpt_dir, start) or {}
    extra = manifest.get("extra", {})
    if fingerprint is not None and "fingerprint" in extra and \
            extra["fingerprint"] != fingerprint:
        logger.warning(
            "checkpoint config fingerprint %r != current %r -- resuming "
            "anyway, but the runs are not comparable",
            extra["fingerprint"], fingerprint)
    data_state = extra.get("data_state")
    if data_state is not None and hasattr(batches, "load_state_dict"):
        batches.load_state_dict(data_state)
        logger.info("data stream cursor restored: %s", data_state)
    elif hasattr(batches, "load_state_dict"):
        logger.warning(
            "checkpoint carries no data cursor: the resumed run will "
            "replay the stream from its current position (sample order "
            "will differ from the uninterrupted run)")
    return state, start


def train_loop(step_fn: Callable, state, batches: Iterator, *,
               total_steps: int, log_every: int = 10,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 500,
               resume: bool = False, tokens_per_step: Optional[int] = None,
               metrics_hook: Optional[Callable] = None,
               keep: int = 3,
               max_consecutive_skips: Optional[int] = 25,
               max_retries: int = 2, retry_backoff_s: float = 0.05,
               watchdog_factor: float = 10.0,
               faults: Optional[FaultInjector] = None,
               config_fingerprint: Optional[str] = None,
               seed: Optional[int] = None):
    """Returns (final_state, history list of metric dicts).

    ``batches`` may be a plain iterator; if it also implements
    ``state_dict``/``load_state_dict`` its cursor is checkpointed and
    restored for exact resume.  ``faults`` defaults to an injector built
    from the ``REPRO_FAULTS`` env var (no-op when unset).
    """
    faults = faults if faults is not None else FaultInjector()
    start = 0
    if resume and ckpt_dir:
        state, start = _resume(state, batches, ckpt_dir, config_fingerprint)

    def _save(done, **extra):
        extra = dict(_checkpoint_extra(batches, state, seed=seed,
                                       fingerprint=config_fingerprint),
                     **extra)
        with TraceAnnotation("train.checkpoint"):
            return save_checkpoint(ckpt_dir, done, state, keep=keep,
                                   extra=extra)

    history = []
    consecutive_skips = total_skips = slow_steps = retries_used = 0
    step = start
    ema_dt: Optional[float] = None
    try:
        window_t0, window_steps = time.time(), 0
        for step in range(start, total_steps):
            with StepTraceAnnotation("train.step", step_num=step + 1):
                with TraceAnnotation("train.data"):
                    batch = next(batches)
                t_step = time.perf_counter()
                faults.maybe_slow(step + 1)  # inside the watchdog's window
                if faults.maybe_nan(step + 1):
                    # forged non-finite step: state kept, update skipped --
                    # the runtime-level mirror of the AMP skip path
                    metrics = {"loss": float("nan"), "skipped": True}
                else:
                    for attempt in range(max_retries + 1):
                        try:
                            with TraceAnnotation("train.dispatch",
                                                 attempt=attempt + 1):
                                faults.maybe_fail(step + 1)
                                state, metrics = step_fn(state, batch)
                            break
                        except TransientStepError as e:
                            if attempt >= max_retries:
                                raise
                            retries_used += 1
                            logger.warning(
                                "step %d attempt %d failed (%s): retrying in "
                                "%.2fs", step + 1, attempt + 1, e,
                                retry_backoff_s * (attempt + 1))
                            time.sleep(retry_backoff_s * (attempt + 1))
                dt = time.perf_counter() - t_step
                window_steps += 1

                # --- non-finite supervision (observes the AMP skip flag) ---
                if max_consecutive_skips is not None:
                    with TraceAnnotation("train.sync"):
                        loss_val = float(np.asarray(metrics.get("loss", 0.0)))
                        skipped = bool(np.asarray(
                            metrics.get("skipped", False)))
                    if skipped or not np.isfinite(loss_val):
                        consecutive_skips += 1
                        total_skips += 1
                        if consecutive_skips > max_consecutive_skips:
                            raise NonFiniteBudgetError(
                                f"{consecutive_skips} consecutive non-finite/"
                                f"skipped steps at step {step + 1} (budget "
                                f"{max_consecutive_skips}): aborting")
                    else:
                        consecutive_skips = 0

                # --- step-duration watchdog (EMA baseline; the
                # compile-bearing first step is excluded from it) ---
                if step - start >= 1:
                    if ema_dt is not None and dt > watchdog_factor * ema_dt:
                        slow_steps += 1
                        logger.warning(
                            "watchdog: step %d took %.3fs (> %.0fx the %.3fs "
                            "EMA) -- straggler or hang?", step + 1, dt,
                            watchdog_factor, ema_dt)
                    else:
                        # slow outliers are excluded from the baseline so one
                        # straggler does not mask the next
                        ema_dt = dt if ema_dt is None else \
                            0.9 * ema_dt + 0.1 * dt

                if (step + 1) % log_every == 0 or step + 1 == total_steps:
                    metrics = {k: float(np.asarray(v))
                               for k, v in metrics.items()}
                    wdt = time.time() - window_t0
                    metrics["steps_per_s"] = window_steps / max(wdt, 1e-9)
                    if tokens_per_step:
                        metrics["tokens_per_s"] = metrics["steps_per_s"] * \
                            tokens_per_step
                    metrics["step"] = step + 1
                    metrics["consecutive_skips"] = consecutive_skips
                    metrics["total_skips"] = total_skips
                    metrics["slow_steps"] = slow_steps
                    metrics["retries"] = retries_used
                    history.append(metrics)
                    logger.info(
                        "step %d | loss %.4f | %s%.1f steps/s",
                        step + 1, metrics.get("loss", float("nan")),
                        (f"{metrics['tokens_per_s']:.0f} tok/s | "
                         if "tokens_per_s" in metrics else ""),
                        metrics["steps_per_s"])
                    if metrics_hook:
                        metrics_hook(metrics)
                    window_t0, window_steps = time.time(), 0
                faults.maybe_crash(step + 1)
                if ckpt_dir and (step + 1) % ckpt_every == 0:
                    path = _save(step + 1)
                    faults.maybe_torn_write(step + 1, path)
    except Exception:
        if ckpt_dir:
            done = step if step < total_steps else total_steps
            try:
                _save(done, emergency=True)
                logger.warning("emergency checkpoint saved at step %d in %s",
                               done, ckpt_dir)
            except Exception as ce:  # noqa: BLE001 -- best effort only
                logger.warning("emergency checkpoint failed: %s", ce)
        raise
    if ckpt_dir and start < total_steps:
        _save(total_steps)
    return state, history
