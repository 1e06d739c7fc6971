"""One run of one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload bert-large.p1-s128 --seed 7 \
      --seconds 15 --trace 0

The cell's configuration, traffic mix, per-layer metrics and limits are
found by name from ``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/limits/<workload>.json``; the configuration's ``family`` names the
module that knows its model, ``bench/families/<family>.py`` (its names are
``FAMILY_NAMES``).  Set-up builds the step and its state, drives the first
steps that the reference follows and a few timed ones, then one
``train_loop`` call runs for the window.  ``--trace 1`` records
the window with the profiler and reports the per-layer metrics instead of
the end-to-end ones.  After the window the plain reference decides
``correct``.  The last line of standard output is the result as JSON; the
numbers compared, each with its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if not __package__:                     # run as a script: import bench as a
    sys.path[0] = str(ROOT)             # package, and the program from src
    sys.path.insert(1, str(ROOT / "src"))
CACHE = ROOT / "bench" / ".cache"

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax._src.lib import _profiler  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import peaks, reference, trace  # noqa: E402
from bench.cell import (  # noqa: E402
    MIN_WINDOW_STEPS, TIMING_STEPS, CompileClock, Probe, TrainCell, peak_bytes)

CHECKS = ("loss_gap", "grad_gap", "grad_err", "change_gap", "rows_missing")
FAMILY_NAMES = ("program_config", "init_weights", "examples", "row_keys",
                "LOSSES", "loss", "train_flops_per_token", "tiny")
PROFILE_OPTIONS = jax.profiler.ProfileOptions()
PROFILE_OPTIONS.python_tracer_level = 0   # no event per Python call


def resolve(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    reports = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m) and m["moves"] in e2e_names]
    read_json = lambda p: json.loads((root / p).read_text())
    cfg = read_json(conf["file"])
    return {"name": name, "chips": wl["chips"], "config": cfg,
            "family": family(cfg["family"], root),
            "mix": read_json(f"bench/traffic/{wl['traffic']}.json"),
            "limits": read_json(f"bench/limits/{name}.json"),
            "end_to_end": e2e, "per_layer": per_layer,
            "metrics_dir": root / "bench" / "metrics"}


def judge(found: dict, limits: dict, failed: int = 0) -> tuple:
    """Each number compared beside its limit, and whether all hold.  A
    number whose limit is null has no upper reading in that cell and is
    not compared (PERF.md names it with its readings)."""
    checks = {k: {"value": found[k], "limit": limits[k]} for k in CHECKS
              if limits[k] is not None}
    return checks, bool(failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))


def load(path: Path, module_name: str):
    """The module in the file ``path``, loaded by its path."""
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str, root: Path = ROOT):
    """The family module ``bench/families/<name>.py`` under ``root``."""
    families = root / "bench" / "families"
    path = families / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in families.glob("*.py"))
        raise SystemExit(f"unknown model family {name!r}; known: {known}")
    mod = load(path, f"bench_family_{name}")
    missing = [n for n in FAMILY_NAMES if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"{path} lacks {missing}")
    return mod


def reader(metrics_dir: Path, name: str):
    return load(metrics_dir / f"{name}.py", f"bench_metric_{name}").read


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept.  The
    key holds the programs' metadata, so an executable compiled before a
    change of op names (named scopes) is not served for the new program."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def run(spec: dict, seed: int, seconds: float, traced: bool, devices,
        t_start: float = T_START) -> dict:
    """One run of the cell ``spec`` on ``devices``; prints and returns the
    result."""
    cfg, mix, fam = spec["config"], spec["mix"], spec["family"]
    chip = peaks.chip_peaks(devices[0].device_kind)
    parts = {"jax_init": time.time() - t_start}
    with CompileClock() as clock:
        t = time.perf_counter()
        cell = TrainCell(fam, cfg, mix, devices, CACHE / "run" / spec["name"])
        parts["build"] = time.perf_counter() - t
        feed, rows, made = cell.start(seed)
        parts.update(made)
        probe = Probe(cell.step, fam.LOSSES)
        t = time.perf_counter()
        prog, batches = cell.first_steps(probe, feed)
        parts["first_steps"] = time.perf_counter() - t
        probe.reset()
        t = time.perf_counter()
        cell.drive(probe, feed, TIMING_STEPS)
        jax.block_until_ready(cell.state)
        parts["timing_steps"] = time.perf_counter() - t
        n_steps = max(MIN_WINDOW_STEPS, math.ceil(
            seconds * TIMING_STEPS / parts["timing_steps"]))
        compile_s, setup_compiles = clock.seconds, clock.compiles
        probe.reset()
        if traced:      # the session's trace is read in memory: no file
            session = _profiler.ProfilerSession(PROFILE_OPTIONS)
        setup_s = time.time() - t_start
        t0 = time.perf_counter()
        with TraceAnnotation(trace.WINDOW_SPAN):
            cell.drive(probe, feed, n_steps)
            jax.block_until_ready(cell.state)
        t1 = time.perf_counter()
        if traced:
            t = time.perf_counter()
            profile = session.stop_and_get_profile_data()
            trace_s = {"stop": time.perf_counter() - t}
        window_compiles = clock.compiles - setup_compiles
    print("setup: " + " | ".join(f"{k} {v:.3f} s" for k, v in parts.items())
          + f" | compile_s {compile_s:.3f} | total {setup_s:.3f} s")

    losses = np.asarray(jax.device_get(probe.losses), np.float64)[:, 0]
    skipped = np.asarray(jax.device_get(probe.skipped), bool)
    failed = int(np.sum(skipped | ~np.isfinite(losses)))
    intervals = np.diff(np.asarray(probe.entries + [t1]))
    tps_chip = n_steps * cell.tokens_per_step / (t1 - t0) / len(cell.devices)
    memory = peak_bytes(cell.devices)
    print(f"window: {n_steps} steps in {t1 - t0:.3f} s | compiles in "
          f"window {window_compiles} | step ms p50 "
          f"{np.median(intervals) * 1e3:.3f} p90 "
          f"{np.percentile(intervals, 90) * 1e3:.3f} max "
          f"{intervals.max() * 1e3:.3f} | loss first {losses[0]:.4f} last "
          f"{losses[-1]:.4f} | peak bytes {memory}")
    cell.state = None
    probe.reset()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(cell.devices),
              "memory_peak_bytes": memory}
    result = {"correct": False, "attempted": n_steps, "failed": failed}
    if traced:
        t = time.perf_counter()
        timelines = trace.read_xspace(profile)
        del profile
        trace_s["read"] = time.perf_counter() - t
        t = time.perf_counter()
        red = trace.reduce_timelines(*timelines, n_steps=n_steps)
        trace_s["reduce"] = time.perf_counter() - t
        n_ops = sum(d["ops"].ids.size for d in timelines[0].values())
        del timelines
        print("trace: " + " | ".join(f"{k} {v:.3f} s" for k, v in
                                     trace_s.items()) + f" | ops {n_ops}")
        info = {"trace": red, "tokens_per_s_per_chip": tps_chip,
                "flops_per_token": fam.train_flops_per_token(cfg, mix),
                "peaks": chip, "compile_s": compile_s}
        metrics = {}
        for m in spec["per_layer"]:
            value = reader(spec["metrics_dir"], m["name"])(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]})
    else:
        values = {"tokens_per_s_per_chip": tps_chip,
                  "step_ms_p90": float(np.percentile(intervals, 90)) * 1e3,
                  "setup_s": setup_s}
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in spec["end_to_end"]}, device=device)

    ref, missing = cell.reference_readings(rows, batches)
    found = dict(reference.compare(prog, ref), rows_missing=missing)
    checks, result["correct"] = judge(found, spec["limits"], failed)
    result["checks"] = checks
    print(f"program losses {prog['losses']} | reference {ref['losses']}")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator ({e})", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < spec["chips"]:
        print(f"bench: {args.workload} needs {spec['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache()
    run(spec, args.seed, args.seconds, bool(args.trace),
        devices[:spec["chips"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
