#!/usr/bin/env bash
# CI entrypoint: tier-1 test suite + a 2-device serve smoke on CPU.
#
#   bash scripts/ci.sh            # everything
#   bash scripts/ci.sh tests      # tier-1 pytest only
#   bash scripts/ci.sh serve      # 2-device serve example smoke only
#   bash scripts/ci.sh paged      # paged KV-cache smoke (tiny pool)
#   bash scripts/ci.sh prefix     # prefix-cache smoke (reclaim-before-preempt)
#   bash scripts/ci.sh faults     # chaos smoke: crash -> resume bit-identical
#   bash scripts/ci.sh multiarch  # one scheduler, every arch family smoke
#   bash scripts/ci.sh train-dp   # 4-device DP train matrix: every collective
#                                 # strategy bit-matches the psum loss, plus a
#                                 # compressed (int8 + error feedback) run
#   bash scripts/ci.sh train-overlap # 4-device overlapped drain schedule:
#                                 # bit-matches serial psum at accum 1/2/4,
#                                 # then an autotuner smoke on a tiny grid
#
# The serve smoke forces 2 host devices so scheduler / sharding regressions
# in the decode path surface without accelerators.  The paged smoke runs the
# continuous scheduler with 2 pages per slot and a deliberately starved pool
# so the PageAllocator's grow/evict/reuse/preempt paths run on every PR.
# The prefix smoke starves the pool under shared-prefix load and asserts the
# cached zero-ref pages are LRU-reclaimed before any slot is preempted.
# The faults smoke hard-kills a training run mid-stream via REPRO_FAULTS,
# resumes from the surviving checkpoint, and asserts the resumed loss
# trajectory is bit-identical to an uninterrupted reference run; it also
# tears the newest checkpoint on disk and asserts restore falls back.
# The multiarch smoke drives the continuous scheduler through one config
# per architecture family (dense, recurrent, hybrid, encoder-decoder) so
# the slot-state contract's admit/prefill/evict paths run on every PR.
# The train-dp step forces 4 host devices and runs 5 real dp_shardmap
# training steps per collective strategy (psum / ppermute ring /
# hierarchical / bucketed-overlap), asserting every strategy's final loss
# BIT-MATCHES the psum reference (the paper's semantics-preserving claim),
# then one int8-compressed exchange run (error feedback on) asserting the
# losses stay finite and land within tolerance of the uncompressed
# trajectory.  Loss logs land in ci-artifacts/ for upload.
# The train-overlap step forces 4 host devices and asserts the overlapped
# drain schedule (TrainConfig.overlap_exchange) produces BIT-IDENTICAL loss
# trajectories to the serial psum reference across accum_steps 1/2/4 and an
# uneven bucket size, then runs the measured comm autotuner
# (repro.tune.autotune) over a tiny grid as a smoke of the search loop.
# The bench-check step validates every BENCH_*.json section against the
# committed schema (scripts/bench_check.py) with --strict: a renamed metric
# or dropped derived block fails the build -- update SCHEMAS in the same PR
# that changes a bench's payload shape.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

step="${1:-all}"
mkdir -p ci-artifacts

if [[ "$step" == "all" || "$step" == "tests" ]]; then
    echo "=== tier-1: pytest ==="
    python -m pytest -x -q
fi

if [[ "$step" == "all" || "$step" == "serve" ]]; then
    echo "=== serve smoke: 2 host devices, cohort + continuous ==="
    export XLA_FLAGS="--xla_force_host_platform_device_count=2${XLA_FLAGS:+ $XLA_FLAGS}"
    python examples/serve.py --mode cohort --batch 2 --prompt-len 8 \
        --new-tokens 4 --requests 4
    python examples/serve.py --mode continuous --batch 2 --prompt-len 8 \
        --new-tokens 4 --requests 4
fi

if [[ "$step" == "all" || "$step" == "paged" ]]; then
    echo "=== paged serving smoke: 2 pages/slot, starved pool (evict+reuse) ==="
    # max_len 16 / page 8 -> 2 pages per slot; 3-page pool < 2 slots x 2
    # pages worst case, 6 requests through 2 slots -> growth, eviction
    # reuse and (if the pool dries mid-decode) preemption all execute
    python examples/serve.py --mode continuous --cache-mode paged_int8 \
        --batch 2 --prompt-len 8 --new-tokens 8 --requests 6 \
        --page-size 8 --num-pages 4
fi

if [[ "$step" == "all" || "$step" == "prefix" ]]; then
    echo "=== prefix-cache smoke: starved pool, reclaim before preemption ==="
    # shared-prefix hits on both paged modes, with hit-rate printout
    python examples/serve.py --mode continuous --cache-mode paged \
        --batch 2 --prompt-len 16 --new-tokens 6 --requests 8 \
        --page-size 8 --prefix-cache
    # starved pool (12 usable pages, <=3 pages/admission): drained requests'
    # zero-ref cached pages MUST be reclaimed to feed later admissions, and
    # must yield before any live slot is preempted
    python - <<'EOF'
import jax, numpy as np
from repro.configs import get_config, smoke_variant
from repro.core.amp import make_policy
from repro.models import transformer as T
from repro.serve.scheduler import ContinuousScheduler, Request

cfg = smoke_variant(get_config("deepseek-7b"))
params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
sched = ContinuousScheduler(
    params, cfg, make_policy("f32"), batch=2, max_len=48, prefill_len=16,
    cache_mode="paged", page_size=8, num_pages=13, prefix_cache=True)
rng = np.random.default_rng(4)
heads = [rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
         for _ in range(3)]
for i in range(9):
    sched.submit(Request(
        rid=i, max_new_tokens=6,
        prompt=np.concatenate([heads[i % 3],
                               rng.integers(0, cfg.vocab_size, size=5,
                                            dtype=np.int32)])))
done = sched.run()
st = sched.stats
print(f"done={len(done)} hit_rate={st.prefix_hit_rate:.2f} "
      f"reclaimed={sched.allocator.reclaimed} preemptions={st.preemptions}")
assert len(done) == 9
assert sched.allocator.reclaimed > 0, "cache never yielded pages"
assert st.preemptions == 0, "preempted a live slot before draining the cache"
assert sched.allocator.in_use == 0, "pages leaked after drain"
EOF
fi

if [[ "$step" == "all" || "$step" == "multiarch" ]]; then
    echo "=== multiarch serving smoke: one scheduler, every arch family ==="
    # dense (attention KV), recurrent (O(1) state, cache_bytes==0), hybrid
    # (mamba state + attention KV), encoder-decoder (per-slot cross cache)
    for arch in deepseek-7b rwkv6-1.6b jamba-1.5-large-398b whisper-small; do
        python examples/serve.py --mode continuous --arch "$arch" \
            --batch 2 --prompt-len 8 --new-tokens 4 --requests 4
    done
    # hybrid paging: only jamba's attention layers page; its mamba state
    # rides the per-slot scatter/reset path alongside the block tables
    python examples/serve.py --mode continuous --arch jamba-1.5-large-398b \
        --cache-mode paged --batch 2 --prompt-len 8 --new-tokens 4 \
        --requests 4 --page-size 8
fi

if [[ "$step" == "all" || "$step" == "faults" ]]; then
    echo "=== faults chaos smoke: crash -> resume, bit-identical losses ==="
    work="$(mktemp -d)"
    trap 'rm -rf "$work"' EXIT
    train_args=(--arch deepseek-7b --steps 7 --batch 2 --seq 32
                --precision f32 --log-every 1 --ckpt-every 3)
    # reference: uninterrupted run
    python -m repro.launch.train "${train_args[@]}" \
        --ckpt-dir "$work/ref_ckpt" --loss-log "$work/ref.jsonl"
    # chaos: hard os._exit at step 5 (no cleanup, no emergency checkpoint --
    # only the atomic checkpoint at step 3 survives)
    set +e
    REPRO_FAULTS="crash_at=5" python -m repro.launch.train \
        "${train_args[@]}" --ckpt-dir "$work/ckpt" --loss-log "$work/loss.jsonl"
    code=$?
    set -e
    [[ "$code" == 43 ]] || { echo "expected crash exit 43, got $code"; exit 1; }
    # resume: must continue from step 3's checkpoint + data cursor
    python -m repro.launch.train "${train_args[@]}" \
        --ckpt-dir "$work/ckpt" --loss-log "$work/loss.jsonl" --resume
    python - "$work" <<'EOF'
import json, sys
from pathlib import Path
work = Path(sys.argv[1])
load = lambda p: {json.loads(l)["step"]: json.loads(l)["loss"]
                  for l in p.read_text().splitlines()}
ref, got = load(work / "ref.jsonl"), load(work / "loss.jsonl")
assert sorted(ref) == list(range(1, 8)), sorted(ref)
for s, loss in ref.items():
    assert got[s] == loss, f"step {s}: resumed {got[s]!r} != ref {loss!r}"
print(f"crash->resume OK: {len(ref)} steps bit-identical")
EOF
    cp "$work"/ref.jsonl ci-artifacts/faults_ref.jsonl
    cp "$work"/loss.jsonl ci-artifacts/faults_resume.jsonl
    echo "=== faults chaos smoke: torn-checkpoint fallback ==="
    python - <<'EOF'
import glob, tempfile
import numpy as np
from pathlib import Path
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.faults import torn_write
d = tempfile.mkdtemp()
tree = {"w": np.arange(6, dtype=np.float32)}
save_checkpoint(d, 1, tree)
p2 = save_checkpoint(d, 2, {"w": tree["w"] * 2})
torn_write(p2, 64)                      # simulate a kill mid-write
assert latest_step(d) == 1, "torn checkpoint not skipped"
got, step = restore_checkpoint(d, tree)
assert step == 1
np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
print("torn-checkpoint fallback OK: restored step 1")
EOF
fi

if [[ "$step" == "all" || "$step" == "train-dp" ]]; then
    echo "=== train-dp matrix: 4 devices, strategies bit-match psum + compressed run ==="
    # device-count flag goes LAST: an earlier step may have exported its own
    # count into XLA_FLAGS and the final occurrence wins
    XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4" \
    python - <<'EOF'
import json
import jax
import numpy as np
from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train.train_step import init_train_state, make_train_step_dp

assert len(jax.devices()) == 4, jax.devices()
cfg = smoke_variant(get_config("bert-large"), d_model=64)
shape = InputShape("ci", 32, 16, "train")
params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
batches = [api.make_synth_batch(jax.random.PRNGKey(i), cfg, shape)
           for i in range(5)]

def run(strategy, comp="none"):
    if strategy == "hierarchical":
        mesh = make_mesh((2, 2), ("pod", "data"))
    else:
        mesh = make_mesh((4,), ("data",))
    tcfg = TrainConfig(precision="f32", accum_steps=1,
                       collective_strategy=strategy, grad_compression=comp,
                       total_steps=50, warmup_steps=2, bucket_bytes=1 << 16)
    step, _ = make_train_step_dp(cfg, tcfg, mesh, shape)
    state = init_train_state(params, make_policy("f32"), tcfg, world=4)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(np.asarray(m["loss"])))
    return losses

log = {}
log["psum"] = ref = run("psum")
for strategy in ("ring", "hierarchical", "bucketed"):
    log[strategy] = got = run(strategy)
    assert got == ref, (
        f"{strategy} loss trajectory diverged from psum:\n{got}\n{ref}")
    print(f"{strategy:12s} == psum  ({len(ref)} steps bit-identical)")
for comp in ("int8",):
    log[f"psum+{comp}"] = got = run("psum", comp)
    assert all(np.isfinite(got)), f"{comp} produced non-finite losses: {got}"
    dev = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    assert dev < 0.02, f"{comp} trajectory drifted {dev:.4f} from psum: {got}"
    print(f"psum+{comp:5s} finite, max rel dev {dev:.2e} (< 0.02)")
with open("ci-artifacts/train_dp_losses.json", "w") as f:
    json.dump(log, f, indent=2)
print("train-dp matrix OK")
EOF
fi

if [[ "$step" == "all" || "$step" == "train-overlap" ]]; then
    echo "=== train-overlap: 4 devices, drain schedule bit-matches serial psum ==="
    XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4" \
    python - <<'EOF'
import json
import jax
import numpy as np
from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape, TrainConfig
from repro.core.amp import make_policy
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.train.train_step import init_train_state, make_train_step_dp

assert len(jax.devices()) == 4, jax.devices()
cfg = smoke_variant(get_config("bert-large"), d_model=64)
shape = InputShape("ci", 32, 16, "train")
params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
batches = [api.make_synth_batch(jax.random.PRNGKey(i), cfg, shape)
           for i in range(5)]

def run(accum, overlap, bucket_bytes=1 << 16, comp="none"):
    tcfg = TrainConfig(precision="f32", accum_steps=accum,
                       collective_strategy="psum", grad_compression=comp,
                       overlap_exchange=overlap, total_steps=50,
                       warmup_steps=2, bucket_bytes=bucket_bytes)
    step, _ = make_train_step_dp(cfg, tcfg, make_mesh((4,), ("data",)), shape)
    state = init_train_state(params, make_policy("f32"), tcfg, world=4)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(np.asarray(m["loss"])))
    return losses

log = {}
for accum in (1, 2, 4):
    ref = run(accum, overlap=False)
    got = run(accum, overlap=True)
    log[f"accum{accum}"] = {"serial": ref, "overlap": got}
    assert got == ref, (
        f"accum={accum}: overlapped diverged from serial psum:\n{got}\n{ref}")
    print(f"accum={accum}: overlapped == serial psum "
          f"({len(ref)} steps bit-identical)")
# uneven bucket boundary (prime size: leaves straddle buckets)
ref = run(2, overlap=False)
got = run(2, overlap=True, bucket_bytes=50021)
assert got == ref, f"uneven buckets diverged:\n{got}\n{ref}"
print("uneven bucket boundaries: bit-identical")
with open("ci-artifacts/train_overlap_losses.json", "w") as f:
    json.dump(log, f, indent=2)
print("train-overlap compare OK")
EOF
    echo "=== train-overlap: autotuner smoke (tiny grid) ==="
    python -m repro.tune.autotune --devices 4 --d-model 32 \
        --iters0 1 --max-rounds 2 --out ci-artifacts/BENCH_autotune_smoke.json \
        --space-json '{"bucket_bytes": [65536], "accum_steps": [1, 2],
                       "strategy": ["psum"], "compression": ["none"],
                       "overlap": [false, true]}'
    python scripts/bench_check.py --strict \
        ci-artifacts/BENCH_autotune_smoke.json
fi

if [[ "$step" == "all" || "$step" == "bench-check" ]]; then
    echo "=== bench schema guard (strict) ==="
    python scripts/bench_check.py --strict
fi

echo "CI OK"
