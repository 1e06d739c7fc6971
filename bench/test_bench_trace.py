"""Interval arithmetic of bench/trace.py on a constructed trace.

Times are in nanoseconds.  Two devices run a step program twice in a
window of 100; device 1 runs an async all-reduce that partly overlaps a
fusion and a synchronous all-gather that nothing overlaps.
"""
import random

import pytest

from bench import trace


def test_union_intersect_subtract():
    assert trace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    assert trace.measure([(0, 4), (2, 6), (10, 11)]) == 7.0
    assert trace.intersect([(0, 10)], [(2, 3), (5, 12)]) == [(2, 3), (5, 10)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 10)], []) == [(0, 10)]
    assert trace.clip([(-5, 5), (20, 30), (95, 120)], (0, 100)) == \
        [(0, 5), (20, 30), (95, 100)]


def test_short_names():
    assert trace.short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %x)") == \
        "fusion.12"
    assert trace.short_name("jit_train_step(123)") == "jit_train_step(123)"


def test_async_collectives_span_start_to_done():
    ops = [("all-reduce-start.1", 10, 12), ("fusion.3", 12, 20),
           ("all-reduce-done.1", 20, 25), ("all-gather.2", 30, 34),
           ("reduce-scatter-start", 40, 41), ("reduce-scatter-done", 45, 46)]
    named = {"all-reduce-start.1", "all-reduce-done.1", "all-gather.2",
             "reduce-scatter-start", "reduce-scatter-done"}
    assert sorted(trace.collective_intervals(ops, named)) == \
        [(10, 25), (30, 34), (40, 46)]
    assert trace.collective_intervals(ops, set()) == []


def _timelines():
    mod = "jit_train_step"
    dev0 = {"ops": [("while.3", 10, 40), ("fusion.1", 10, 40),
                    ("fusion.2", 40, 45), ("fusion.1", 60, 90)],
            "modules": [(mod, 10, 45), ("jit_small", 50, 52), (mod, 60, 90)]}
    dev1 = {"ops": [("fusion.1", 10, 30), ("all-reduce-start.7", 30, 31),
                    ("fusion.2", 31, 35), ("all-reduce-done.7", 35, 40),
                    ("all-gather.4", 40, 44), ("fusion.1", 60, 90)],
            "async": [("all-reduce-start.7", 30, 38)],
            "modules": [(mod, 10, 44), (mod, 60, 90)],
            "collectives": {"all-reduce-start.7", "all-reduce-done.7",
                            "all-gather.4"}}
    host = [("data.next", 0, 8), ("step.call", 8, 12), ("data.next", 45, 50),
            ("step.call", 50, 58), ("data.next", 92, 96)]
    return {0: dev0, 1: dev1}, host, (0, 100)


def test_reduce_timelines():
    devices, host, window = _timelines()
    out = trace.reduce_timelines(devices, host, window, n_steps=2)
    assert out["window_s"] == pytest.approx(100e-9)
    # busy: device 0 35 + 30 = 65, device 1 34 + 30 = 64
    assert out["busy_s"] == pytest.approx(64.5e-9)
    # gaps between runs of the step program: 15 and 16, over 2 steps
    assert out["host_gap_ms"] == pytest.approx(15.5 / 2 * 1e-6)
    # device 1: all-reduce in flight 30..40 and all-gather 40..44 = 14;
    # fusion.2 covers 31..35, so 10 of them are exposed
    assert out["exchange_ms"] == pytest.approx(14 / 2 * 1e-6)
    assert out["exchange_exposed_ms"] == pytest.approx(10 / 2 * 1e-6)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 30 + 20 + 30) / 2 * 1e-9)
    assert list(ops)[0] == "fusion.1" and "while.3" not in ops


def test_idle_gaps_by_host_span():
    devices, host, window = _timelines()
    # device 0 is idle 0..10, 45..60 and 90..100
    gaps = dict(trace.idle_by_host(devices[0], host, window))
    assert gaps["data.next"] == pytest.approx((8 + 5 + 4) * 1e-9)
    assert gaps["step.call"] == pytest.approx((2 + 8) * 1e-9)
    assert gaps["loop.host"] == pytest.approx((2 + 6) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(35e-9)


def test_no_collectives_and_no_devices():
    devices, host, window = _timelines()
    out = trace.reduce_timelines({0: devices[0]}, host, window, n_steps=2)
    assert "exchange_ms" not in out and "exchange_exposed_ms" not in out
    empty = trace.reduce_timelines({}, host, window, n_steps=2)
    assert empty["busy_s"] == 0.0 and empty["device_ops"] == []


def test_a_loop_around_a_collective_does_not_hide_it():
    """A ``while`` event spans its body; it is no work beside the exchange:
    the all-reduce in 20..30 is exposed but for fusion.2's 24..26."""
    dev = {"ops": [("while.1", 0, 40), ("fusion.1", 0, 20),
                   ("all-reduce.3", 20, 30), ("fusion.2", 24, 26),
                   ("fusion.1", 30, 40)],
           "modules": [("jit_train_step", 0, 40)],
           "collectives": {"all-reduce.3"}}
    out = trace.reduce_timelines({0: dev}, [], (0, 50), n_steps=1)
    assert out["exchange_ms"] == pytest.approx(10 * 1e-6)
    assert out["exchange_exposed_ms"] == pytest.approx(8 * 1e-6)


@pytest.mark.parametrize("text, kind", [
    ("%psum.211 = f32[24,16,64,1024]{3,2,1,0:T(8,128)S(1)} all-reduce("
     "%add_multiply_fusion.5), channel_id=1, replica_groups={{0,1,2,3}}",
     "all-reduce"),
    ("%all-reduce.2 = (f32[24,1024]{1,0:T(8,128)}, f32[2]{0:T(128)}) "
     "all-reduce(%a, %b), channel_id=2", "all-reduce"),
    ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x)",
     "all-reduce-start"),
    ("%fusion.12 = f32[8]{0} fusion(f32[8] %x), kind=kLoop", "fusion"),
    ("jit_train_step(123)", ""),
])
def test_opcode(text, kind):
    assert trace.opcode(text) == kind


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 50000 duration_ps: 30000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 80000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2
    name: "%psum.211 = f32[8]{0:T(8)S(1)} all-reduce(%fusion.1)" } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %psum.211)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_train_step(1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_an_all_reduce_named_after_its_source_is_an_exchange():
    """The compiler names a psum's all-reduce ``psum.211``: its opcode in
    the event's HLO text, not its name, makes it an exchange."""
    from jax.profiler import ProfileData
    devices, host, window = trace.read_xspace(
        ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(XSPACE)))
    assert devices[0]["collectives"] == {"psum.211"}
    assert [n for n, _, _ in trace.events(devices[0]["ops"])] == \
        ["fusion.1", "psum.211", "fusion.2"]
    out = trace.reduce_timelines(devices, host, window, n_steps=1)
    assert window == (0, 100)
    assert out["exchange_ms"] == pytest.approx(10 * 1e-6)
    assert out["exchange_exposed_ms"] == pytest.approx(10 * 1e-6)


def _naive_subtract(a, b) -> list:
    """The parts of ``a`` that ``b`` does not cover, interval by interval."""
    out = []
    for s, e in trace.union(a):
        cur = s
        for bs, be in trace.union(b):
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


@pytest.mark.parametrize("seed", range(3))
def test_subtract_sweeps_as_the_naive_loop_does(seed):
    r = random.Random(seed)

    def cover(n, span):
        out = []
        for _ in range(n):
            s = r.randint(0, 1000)
            out.append((s, s + r.randint(1, span)))
        return out

    a, b = cover(60, 80), cover(200, 15)
    assert trace.subtract(a, b) == _naive_subtract(a, b)
    assert trace.subtract(b, a) == _naive_subtract(b, a)


@pytest.mark.parametrize("seed", range(3))
def test_column_union_and_cover_agree_with_the_lists(seed):
    import numpy as np
    r = random.Random(seed)
    pairs = []
    for _ in range(300):
        s = r.uniform(0, 1000)
        pairs.append((s, s + r.choice([0.0, r.uniform(0, 20)])))
    start, end = trace._union(*np.array(pairs).T)
    assert list(zip(start.tolist(), end.tolist())) == trace.union(pairs)
    spans = trace.union([(s, s + r.uniform(0, 60))
                         for s in (r.uniform(-50, 1050) for _ in range(40))])
    assert trace._covered(start, end, spans) == pytest.approx(
        trace.measure(pairs) - trace.measure(trace.subtract(pairs, spans)),
        rel=1e-12)


def test_columns_and_lists_reduce_alike():
    devices, host, window = _timelines()
    columns = {k: dict(d, ops=trace.timeline(d["ops"]))
               for k, d in devices.items()}
    assert trace.reduce_timelines(columns, host, window, n_steps=2) == \
        trace.reduce_timelines(devices, host, window, n_steps=2)
    assert trace.events(columns[1]["ops"]) == devices[1]["ops"]


def test_a_collective_is_known_by_opcode_not_by_name():
    """An op named like a collective is work, unless its opcode made it
    one of the device's ``collectives``."""
    devices, host, window = _timelines()
    devices[1]["collectives"] = set()
    out = trace.reduce_timelines(devices, host, window, n_steps=2)
    assert "exchange_ms" not in out
