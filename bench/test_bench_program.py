"""bench/program.py on a constructed trace.

Times are in nanoseconds.  A window of 200 holds two ``train.step`` spans;
the second retries its dispatch and saves a checkpoint.  The benchmark's
own ``data.next`` and ``step.call`` spans nest inside the program's, as
they do when ``bench/cell.py`` drives ``train_loop``.  Device 0 is idle
0..25, 85..130 and 178..195.
"""
import json
import random

import pytest

from bench import program, trace

WINDOW = (0, 200)
BENCH_HOST = [("data.next", 1, 9), ("step.call", 11, 29),
              ("data.next", 101, 104), ("step.call", 106, 119),
              ("step.call", 123, 139)]
PROGRAM_HOST = [("train.step", 0, 100), ("train.data", 0, 10),
                ("train.dispatch", 10, 30), ("train.sync", 30, 90),
                ("train.step", 100, 190), ("train.data", 100, 105),
                ("train.dispatch", 105, 120), ("train.dispatch", 122, 140),
                ("train.sync", 140, 180), ("train.checkpoint", 180, 188)]
OPS = [("while.1", 25, 85), ("fusion.1", 25, 50), ("fusion.2", 50, 70),
       ("fusion.3", 70, 85), ("fusion.1", 130, 150), ("fusion.4", 150, 175),
       ("copy.1", 175, 178), ("fusion.5", 195, 205)]
OP_NAMES = {
    "while.1": "jit(train_step)/while",
    "fusion.1": "jit(train_step)/jvp()/while/body/closed_call/"
                "bert.attention/dot_general",
    "fusion.2": "jit(train_step)/transpose(jvp(bert.ffn))/dot_general",
    "fusion.3": "jit(train_step)/lamb/sqrt",
    "fusion.4": "jit(train_step)/clip/mul",
    "copy.1": "jit(train_step)/jvp(bert.heads)/copy",
    "fusion.5": "jit(train_step)/jvp(bert.embed)/add",
}
IDLE = {"train.data": 10 + 5, "train.dispatch": 15 + 15 + 8,
        "train.sync": 5 + 2, "train.checkpoint": 8,
        "loop": 10 + 2 + 2, "outside": 5}
BUSY = {"bert.embed": 5, "bert.attention": 25 + 20, "bert.ffn": 20,
        "bert.heads": 3, "lamb": 15, "other": 25}


def _device(op_names=True) -> dict:
    d = {"ops": list(OPS), "async": [], "modules": [
        ("jit_train_step", 25, 85), ("jit_train_step", 130, 178)]}
    if op_names:
        d["op_names"] = dict(OP_NAMES)
    return d


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/bert.ffn/dot_general",
     "bert.ffn"),
    ("jit(train_step)/transpose(jvp(bert.attention))/dot_general",
     "bert.attention"),
    ("jit(train_step)/jvp(bert.heads)/log_softmax", "bert.heads"),
    ("jit(train_step)/jvp(bert.embed)", "bert.embed"),
    ("jit(train_step)/lamb/sqrt", "lamb"),
    ("jit(train_step)/bert.heads/lamb/add", "lamb"),
    ("jit(train_step)/lamb_update/add", "other"),
    ("jit(train_step)/bert.embedding/add", "other"),
    ("", "other"),
])
def test_scope_of(op_name, scope):
    assert program.scope_of(op_name) == scope


def test_idle_parts_sum_exactly_to_the_idle_time():
    parts = program.split_idle(OPS, BENCH_HOST + PROGRAM_HOST, WINDOW)
    assert parts == IDLE
    idle = trace.subtract([WINDOW], trace.clip(
        [(s, e) for _, s, e in OPS], WINDOW))
    assert sum(parts.values()) == trace.measure(idle) == 87


def _random_run(seed: int) -> tuple:
    """A loop of 40 steps with retried dispatches and checkpoints (one
    after the last step), ops with gaps, and a window that cuts both."""
    r = random.Random(seed)
    ops, host, t = [], [], r.randint(0, 50)
    for _ in range(40):
        s0 = t1 = t + r.randint(0, 3)
        t1 += r.randint(1, 20)
        host.append(("train.data", s0, t1))
        for _ in range(r.randint(1, 3)):
            host.append(("train.dispatch", t1, t1 + r.randint(1, 30)))
            t1 = host[-1][2] + r.randint(0, 5)
        host.append(("train.sync", t1 + 1, t1 + r.randint(2, 60)))
        t1 = host[-1][2]
        if r.random() < 0.2:
            host.append(("train.checkpoint", t1, t1 + 7))
            t1 += 7
        t = t1 + r.randint(0, 9)
        host.append(("train.step", s0 - r.randint(0, 2), t))
    host.append(("train.checkpoint", t + 2, t + 30))
    u = 0
    while u < t + 40:
        d = r.randint(1, 15)
        ops.append(("fusion.1", u, u + d))
        u += d + r.choice([0, 0, 1, 5, 20])
    return ops, host, (r.randint(0, 30), t + r.randint(0, 40))


@pytest.mark.parametrize("seed", range(4))
def test_idle_split_takes_the_innermost_span(seed):
    """Against the definition: each child span takes the idle time it
    covers, then the steps take what is left, then ``outside``."""
    ops, host, window = _random_run(seed)
    left = trace.subtract([window], trace.clip(
        [(s, e) for _, s, e in ops], window))
    want = {}
    for name in program.CHILD_SPANS + (program.STEP_SPAN,):
        spans = [(s, e) for n, s, e in host if n == name]
        part = "loop" if name == program.STEP_SPAN else name
        want[part] = trace.measure(trace.intersect(left, spans))
        left = trace.subtract(left, spans)
    want["outside"] = trace.measure(left)
    assert program.split_idle(ops, host, window) == want


def test_idle_without_program_spans_is_all_outside():
    parts = program.split_idle(OPS, BENCH_HOST, WINDOW)
    assert parts["outside"] == 87
    assert sum(v for k, v in parts.items() if k != "outside") == 0


def test_scopes_and_other_sum_to_busy_time():
    busy = program.split_busy(OPS, OP_NAMES, WINDOW)
    assert busy == BUSY
    union = trace.union(trace.clip(
        [(s, e) for n, s, e in OPS if n != "while.1"], WINDOW))
    assert sum(busy.values()) == trace.measure(union) == 113


def test_ops_without_op_names_go_to_other():
    busy = program.split_busy(OPS, {}, WINDOW)
    assert busy["other"] == 113 and busy["lamb"] == 0


def test_scope_found_once_per_distinct_op(monkeypatch):
    calls = []
    real = program.scope_of
    monkeypatch.setattr(program, "scope_of",
                        lambda n: calls.append(n) or real(n))
    program.split_busy(OPS * 50, OP_NAMES, WINDOW)
    assert len(calls) == len({n for n, _, _ in OPS if n != "while.1"})


def test_reduce_program_per_step():
    dev1 = _device()
    dev1["ops"] = [("fusion.3", 0, 31)]     # lamb only, 31 ns
    out = program.reduce_program({0: _device(), 1: dev1},
                                 BENCH_HOST + PROGRAM_HOST, WINDOW,
                                 n_steps=2)
    assert out["idle_ms"] == pytest.approx(
        {k: v / 2 * 1e-6 for k, v in IDLE.items()})
    want = {k: v / 2 for k, v in BUSY.items()}     # over two devices
    want["lamb"] += 31 / 2
    assert out["busy_ms"] == pytest.approx(
        {k: v / 2 * 1e-6 for k, v in want.items()})
    m = program.metrics(out)
    assert m["idle_dispatch_ms"] == pytest.approx(38 / 2 * 1e-6)
    assert m["idle_loop_ms"] == pytest.approx(14 / 2 * 1e-6)
    assert m["lamb_ms"] == pytest.approx(want["lamb"] / 2 * 1e-6)
    assert set(m) == {"idle_data_ms", "idle_dispatch_ms", "idle_sync_ms",
                      "idle_loop_ms", "attention_ms", "ffn_ms", "lamb_ms"}


def test_reduce_program_without_devices():
    out = program.reduce_program({}, PROGRAM_HOST, WINDOW, n_steps=2)
    assert out["idle_ms"] == {} and program.metrics(out) == {}


@pytest.mark.parametrize("n_steps", [1, 2])
def test_reduce_timelines_ignores_program_spans_and_op_names(n_steps):
    plain = trace.reduce_timelines({0: _device(op_names=False)}, BENCH_HOST,
                                   WINDOW, n_steps=n_steps)
    marked = trace.reduce_timelines({0: _device()},
                                    BENCH_HOST + PROGRAM_HOST, WINDOW,
                                    n_steps=n_steps)
    assert marked == plain
    assert {"host_gap_ms", "busy_s", "device_ops", "idle_gaps"} <= set(plain)


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000 }
    events { metadata_id: 2 offset_ps: 4000 duration_ps: 3000 }
    events { metadata_id: 3 offset_ps: 7000 duration_ps: 2000
             stats { metadata_id: 8
                     str_value: "jit(train_step)/jvp(bert.heads)/dot" } }
    events { metadata_id: 4 offset_ps: 9000 duration_ps: 1000 }
    events { metadata_id: 1 offset_ps: 20000 duration_ps: 4000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 7 str_value: "jit(train_step)/lamb/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy(%p)"
    stats { metadata_id: 8 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3
    name: "%convolution.3 = f32[8]{0} convolution(%p)" } }
  event_metadata { key: 4 value { id: 4
    name: "%fusion.4 = f32[8]{0} fusion(%p)"
    stats { metadata_id: 10 str_value: "kLoop" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_train_step(1)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(train_step)/transpose(jvp(bert.attention))/dot:" } }
  stat_metadata { key: 10 value { id: 10 name: "hlo_category" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 900000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 14000000 }
    events { metadata_id: 4 offset_ps: 100000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "train.step" } }
  event_metadata { key: 2 value { id: 2 name: "train.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "train.step" } }
  event_metadata { key: 4 value { id: 4 name: "step.call" } }
}
"""


def _write_trace(trace_dir) -> None:
    from jax.profiler import ProfileData
    run = trace_dir / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))


def test_read_program_takes_op_names_from_event_or_metadata_stats(
        tmp_path):
    _write_trace(tmp_path)
    devices, host, window = program.read_program(tmp_path)
    dev = devices[0]
    assert dev["op_names"] == {
        "fusion.1": "jit(train_step)/lamb/mul:",         # metadata stat
        "copy.2": "jit(train_step)/transpose(jvp(bert.attention))/dot:",
        "convolution.3": "jit(train_step)/jvp(bert.heads)/dot",  # event stat
        "fusion.4": ""}                                  # none
    assert [n for n, _, _ in dev["ops"]] == [
        "fusion.1", "copy.2", "convolution.3", "fusion.4", "fusion.1"]
    assert dev["modules"] == [("jit_train_step(1)", 1000, 1010)]
    assert sorted(n for n, _, _ in host) == [
        "step.call", "train.dispatch", "train.step", "train.step"]
    assert window == (0, 26000)     # no bench.window: the steps' stretch
    busy = program.reduce_program(devices, host, window, 2)["busy_ms"]
    assert busy["lamb"] == pytest.approx(8 / 2 * 1e-6)
    assert busy["bert.attention"] == pytest.approx(3 / 2 * 1e-6)
    assert busy["other"] == pytest.approx(1 / 2 * 1e-6)


def test_cli_reduces_a_trace_directory(tmp_path, capsys):
    _write_trace(tmp_path)
    assert program.main([str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["n_steps"] == 2
    assert out["metrics"]["lamb_ms"] == pytest.approx(8 / 2 * 1e-6)
    assert program.main([]) == 2
