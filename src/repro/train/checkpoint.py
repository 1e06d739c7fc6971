"""Checkpointing: atomic, verifiable TrainState snapshots (.npz + manifest).

Single-container-per-step layout (mirrors the data sharder's philosophy);
restores onto any mesh because arrays are saved unsharded (fine at the
scales the examples train; production would reuse the shard writer).

Crash-safety contract (the fault-tolerant training runtime leans on this;
``tests/test_faults.py`` and the ``faults`` CI step prove it):

* **Atomic writes.** Both the ``.npz`` payload and the ``.json`` manifest
  are written to a temp file in the same directory, fsync'd, then renamed
  over the final name (rename is atomic on POSIX).  The manifest is written
  *after* the payload, so its presence is the commit marker: a crash at any
  byte offset leaves either the previous checkpoint set intact or a stray
  ``*.tmp`` that the next save sweeps up -- never a half-written file under
  a final name.

* **Verifiable payloads.**  The manifest records, per flattened leaf:
  ``names`` (pytree key paths), ``shapes``, ``dtypes`` and ``checksums``
  (crc32 of the raw array bytes), plus the step, a caller-supplied
  ``extra`` dict (data-loader cursor, RNG/seed, AMP loss-scale scalars,
  config fingerprint -- see ``train/trainer.py``) and ``format: 2``.
  ``validate_checkpoint`` re-derives all of it from the ``.npz`` and
  rejects torn, truncated or bit-flipped files.

* **Fallback restore.**  ``latest_step`` returns the newest *valid* step;
  ``restore_checkpoint`` walks checkpoints newest-to-oldest, loudly
  ``logger.warning``-ing and skipping any that fail validation, and raises
  ``FileNotFoundError`` only when no valid checkpoint exists at all --
  callers can therefore distinguish "nothing to resume" (start fresh) from
  "latest is torn" (fall back to the previous good one) without ever
  silently restarting from step 0.

Manifest schema (``ckpt_{step:08d}.json``)::

    {"format": 2, "step": int,
     "names":  [pytree key path per leaf],
     "shapes": [[dims] per leaf], "dtypes": [str per leaf],
     "checksums": [crc32 of leaf bytes],
     "extra": {...caller metadata, JSON-serializable...}}

Format-1 manifests (pre-fault-tolerance: just ``{"step", "names"}``) are
still restorable; they validate by loadability alone (no checksums).
"""
from __future__ import annotations

import json
import os
import re
import zipfile
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.utils import logger


def _key_to_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _fsync_replace(tmp: Path, final: Path) -> None:
    """fsync ``tmp`` then atomically rename it over ``final``."""
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, final)


def _fsync_dir(d: Path) -> None:
    """Best-effort directory fsync so the renames themselves are durable."""
    try:
        fd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:  # not supported on every platform/filesystem
        pass


def _npz_path(ckpt_dir, step: int) -> Path:
    return Path(ckpt_dir) / f"ckpt_{step:08d}.npz"


def _manifest_path(ckpt_dir, step: int) -> Path:
    return Path(ckpt_dir) / f"ckpt_{step:08d}.json"


def save_checkpoint(ckpt_dir: str, step: int, tree: Any, *,
                    keep: int = 3, extra: Optional[Dict] = None) -> Path:
    """Atomically write ``tree`` as checkpoint ``step``; returns npz path.

    ``extra`` is an arbitrary JSON-serializable dict stored in the manifest
    (data-loader cursor, config fingerprint, loss-scale scalars, ...) and
    returned by ``load_manifest`` / used by the trainer's exact resume.
    """
    out = Path(ckpt_dir)
    out.mkdir(parents=True, exist_ok=True)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [np.asarray(leaf) for _, leaf in flat]
    arrays = {f"a{i:06d}": a for i, a in enumerate(leaves)}
    manifest = {
        "format": 2,
        "step": int(step),
        "names": [_key_to_str(path) for path, _ in flat],
        "shapes": [list(a.shape) for a in leaves],
        "dtypes": [str(a.dtype) for a in leaves],
        "checksums": [zlib.crc32(np.ascontiguousarray(a).tobytes())
                      for a in leaves],
        "extra": extra or {},
    }
    npz, man = _npz_path(out, step), _manifest_path(out, step)
    tmp_npz = npz.with_suffix(".npz.tmp")
    tmp_man = man.with_suffix(".json.tmp")
    with open(tmp_npz, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp_npz, npz)
    # manifest second: its presence commits the checkpoint
    with open(tmp_man, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp_man, man)
    _fsync_dir(out)
    _retain(out, keep)
    return npz


def _retain(out: Path, keep: int) -> None:
    """Keep the newest ``keep`` committed checkpoints; sweep stray tmps."""
    for stray in out.glob("*.tmp"):
        stray.unlink(missing_ok=True)
    steps = sorted(_all_steps(out))
    for s in steps[:-keep] if keep > 0 else []:
        _npz_path(out, s).unlink(missing_ok=True)
        _manifest_path(out, s).unlink(missing_ok=True)


def _all_steps(ckpt_dir) -> List[int]:
    steps = set()
    for p in Path(ckpt_dir).glob("ckpt_*.npz"):
        m = re.match(r"ckpt_(\d+)\.npz$", p.name)
        if m:
            steps.add(int(m.group(1)))
    return sorted(steps)


def load_manifest(ckpt_dir: str, step: int) -> Optional[Dict]:
    """Parse the manifest for ``step`` (None if missing/unparseable)."""
    man = _manifest_path(ckpt_dir, step)
    try:
        return json.loads(man.read_text())
    except (OSError, json.JSONDecodeError):
        return None


def validate_checkpoint(ckpt_dir: str, step: int) -> bool:
    """True iff checkpoint ``step`` is complete and uncorrupted.

    Format-2: manifest parses, npz holds every named array, and each
    array's shape/dtype/crc32 matches the manifest.  Format-1 (legacy, no
    checksums): npz merely has to load with the manifest's leaf count.
    """
    manifest = load_manifest(ckpt_dir, step)
    if manifest is None or "names" not in manifest:
        return False
    npz = _npz_path(ckpt_dir, step)
    try:
        with np.load(npz) as z:
            n = len(manifest["names"])
            if manifest.get("format", 1) < 2:
                return all(f"a{i:06d}" in z.files for i in range(n))
            for i in range(n):
                a = z[f"a{i:06d}"]
                if list(a.shape) != manifest["shapes"][i]:
                    return False
                if str(a.dtype) != manifest["dtypes"][i]:
                    return False
                if zlib.crc32(np.ascontiguousarray(a).tobytes()) != \
                        manifest["checksums"][i]:
                    return False
    except (OSError, ValueError, KeyError, EOFError,
            zipfile.BadZipFile, zlib.error):
        return False
    return True


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest step whose checkpoint validates (torn/truncated ones are
    skipped with a warning -- the fallback the trainer's resume relies on)."""
    for step in reversed(_all_steps(ckpt_dir)):
        if validate_checkpoint(ckpt_dir, step):
            return step
        logger.warning(
            "checkpoint step %d in %s failed validation (torn/truncated "
            "write?): falling back to the previous checkpoint", step,
            ckpt_dir)
    return None


def restore_checkpoint(ckpt_dir: str, like: Any,
                       step: Optional[int] = None) -> Tuple[Any, int]:
    """Restore into the structure of ``like`` (names/shapes/dtypes checked).

    With ``step=None`` walks checkpoints newest-to-oldest, skipping invalid
    ones loudly; raises ``FileNotFoundError`` when no valid checkpoint
    exists (callers treat that as "start fresh").  An explicit ``step``
    must validate or a ``ValueError`` is raised.
    """
    if step is not None:
        if not validate_checkpoint(ckpt_dir, step):
            raise ValueError(
                f"checkpoint step {step} in {ckpt_dir} is missing or "
                "corrupt")
        candidates = [step]
    else:
        candidates = []
        for s in reversed(_all_steps(ckpt_dir)):
            if validate_checkpoint(ckpt_dir, s):
                candidates.append(s)
            else:
                logger.warning(
                    "skipping corrupt checkpoint step %d in %s", s, ckpt_dir)
    flat, treedef = jax.tree_util.tree_flatten(like)
    last_err: Optional[Exception] = None
    for s in candidates:
        try:
            manifest = load_manifest(ckpt_dir, s) or {}
            names = manifest.get("names")
            if names is not None and len(names) != len(flat):
                raise ValueError(
                    f"checkpoint has {len(names)} leaves, expected "
                    f"{len(flat)} (structure mismatch)")
            with np.load(_npz_path(ckpt_dir, s)) as z:
                leaves = [z[f"a{i:06d}"] for i in range(len(flat))]
            for i, (got, want) in enumerate(zip(leaves, flat)):
                if got.shape != tuple(want.shape):
                    raise ValueError(
                        f"leaf {i} ({names[i] if names else '?'}): "
                        f"shape {got.shape} != expected {tuple(want.shape)}")
            # back onto the template's placement, so a step compiled for
            # it takes the restored state without compiling again
            restored = [jax.device_put(np.asarray(g, dtype=w.dtype),
                                       getattr(w, "sharding", None))
                        for g, w in zip(leaves, flat)]
            return jax.tree_util.tree_unflatten(treedef, restored), s
        except (OSError, ValueError, KeyError, EOFError,
                zipfile.BadZipFile) as e:
            last_err = e
            logger.warning("failed to restore checkpoint step %d in %s "
                           "(%s): trying the previous one", s, ckpt_dir, e)
    if last_err is not None:
        raise FileNotFoundError(
            f"no restorable checkpoint in {ckpt_dir} "
            f"(last error: {last_err})")
    raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
