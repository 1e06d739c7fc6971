"""Readings that the limits of ``correct`` are set from, for one cell.

  python bench/control.py --workload bert-large.p1-s128 --seeds 1,2,3 \
      --what program,control,faults

In one process, for each seed, set-up builds the cell's step and state and
drives its first steps exactly as ``bench/run.py`` does (no window is
needed for a training cell), then compares against the plain reference and
judges each reading by the cell's limits (``bench/limits/<cell>.json``)
with ``bench/run.py``'s own ``judge``:

* ``program``: the program itself; the largest reading over a dozen seeds
  or more is the lower reading of each limit.
* ``control``: the reference with every value the program holds in bf16
  rounded to fp8 (the precision below the configuration's), gradients
  too, put in the program's place.
* ``faults``: the reference put in the program's place with half of each
  batch left out (the mean taken over the rest) and, on a data-parallel
  mesh, with only the first worker's rows (the gradient exchange left
  out).  A state left unchanged reads 1 on change_gap and needs no run.

Each seed prints one JSON line, every reading with its ``correct``; the
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

if not __package__:                     # run as a script
    ROOT = Path(__file__).resolve().parent.parent
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402

from bench import reference  # noqa: E402
from bench.cell import Probe, TrainCell  # noqa: E402
from bench.run import CACHE, judge, resolve, use_compile_cache  # noqa: E402


def readings(cell: TrainCell, seed: int, what: set, limits: dict) -> dict:
    feed, rows, _ = cell.start(seed)
    prog, batches = cell.first_steps(Probe(cell.step, cell.family.LOSSES),
                                     feed)
    cell.state = None
    ref, missing = cell.reference_readings(rows, batches)
    out = {"seed": seed}

    def judged(found: dict, missing: int = 0) -> dict:
        found = dict(found, rows_missing=missing)
        return dict(found, correct=judge(found, limits)[1])

    if "program" in what:
        out["program"] = judged(reference.compare(prog, ref), missing)
    variants = {}
    if "control" in what:
        variants["control"] = {"rq": reference.fp8_round}
    if "faults" in what:
        variants["half_batch"] = {"row_share": 0.5}
        dp = cell.mix["data_parallel"]
        if dp > 1:
            variants["no_exchange"] = {"row_share": 1.0 / dp}
    for name, kw in variants.items():
        got, _ = cell.reference_readings(rows, batches, **kw)
        out[name] = judged(reference.compare(got, ref))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--what", default="program,control,faults")
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    use_compile_cache()
    cell = TrainCell(spec["family"], spec["config"], spec["mix"],
                     devices[:spec["chips"]], CACHE / "run" / spec["name"])
    what = set(args.what.split(","))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(cell, seed, what, spec["limits"])
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
