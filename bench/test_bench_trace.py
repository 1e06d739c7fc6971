"""Interval arithmetic of bench/trace.py on a constructed trace.

Times are in nanoseconds.  Two devices run a step program twice in a
window of 100; device 1 runs an async all-reduce that partly overlaps a
fusion and a synchronous all-gather that nothing overlaps.
"""
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
    assert sorted(trace.collective_intervals(ops)) == \
        [(10, 25), (30, 34), (40, 46)]


def _timelines():
    mod = "jit_train_step"
    dev0 = {"ops": [("while.3", 10, 40), ("fusion.1", 10, 40),
                    ("fusion.2", 40, 45), ("fusion.1", 60, 90)],
            "modules": [(mod, 10, 45), ("jit_small", 50, 52), (mod, 60, 90)]}
    dev1 = {"ops": [("fusion.1", 10, 30), ("all-reduce-start.7", 30, 31),
                    ("fusion.2", 31, 35), ("all-reduce-done.7", 35, 40),
                    ("all-gather.4", 40, 44), ("fusion.1", 60, 90)],
            "async": [("all-reduce-start.7", 30, 38)],
            "modules": [(mod, 10, 44), (mod, 60, 90)]}
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
