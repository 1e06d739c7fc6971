"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A trace is read with ``jax.profiler.ProfileData``.  Each TPU device is a
plane named ``/device:TPU:<n>``: its ``XLA Ops`` line holds one event per
operation (a ``while`` loop's event spans the events of its body), its
``Async XLA Ops`` line the time async operations are in flight, its
``XLA Modules`` line one event per program run.  An operation's event name
is its HLO text; only the instruction name before `` = `` is kept.  Host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines, on
the same clock.  All interval arithmetic works on (start, end) pairs in
nanoseconds.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
LINES = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
ASYNC_END = re.compile(r"-(start|done)(?=[.\d]|$)")
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("data.next", "step.call")
OTHER_HOST = "loop.host"
TOP = 10


def union(intervals) -> list:
    """Sorted, disjoint cover of ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def intersect(a, b) -> list:
    """Intersection of two interval sets."""
    a, b, out, i, j = union(a), union(b), [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    """The parts of ``a`` that ``b`` does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def collective_intervals(ops) -> list:
    """Time a collective is in flight: a synchronous collective op's own
    interval, or an async pair's span from its ``-start`` to its ``-done``."""
    out, open_starts = [], defaultdict(list)
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        if not COLLECTIVE.search(name):
            continue
        m = ASYNC_END.search(name)
        if m is None:
            out.append((s, e))
            continue
        key = ASYNC_END.sub("", name)
        if m.group(1) == "start":
            open_starts[key].append(s)
        elif open_starts[key]:
            out.append((open_starts[key].pop(0), e))
        else:
            out.append((s, e))
    return out


def reduce_timelines(devices: dict, host: list, window, n_steps: int
                     ) -> dict:
    """Numbers of one traced window.

    ``devices``: {index: {"ops": [(name, start, end)], "async": [...],
    "modules": [...]}}, names short;
    ``host``: [(span name, start, end)]; ``window``: (start, end) of the
    measured window on the same clock; ``n_steps``: steps in the window.
    """
    window_ns = window[1] - window[0]
    out = {"window_s": window_ns * 1e-9, "n_devices": len(devices)}
    if not devices:
        out.update(busy_s=0.0, device_ops=[], idle_gaps=[])
        return out
    busy, gaps, xch, exposed, op_time = [], [], [], [], defaultdict(float)
    for d in devices.values():
        ops = [(n, s, e) for n, s, e in d["ops"] if e > window[0]
               and s < window[1]]
        busy_iv = union(clip([(s, e) for _, s, e in ops], window))
        busy.append(measure(busy_iv))
        for n, s, e in ops:
            if not CONTAINER.match(n):
                lo, hi = max(s, window[0]), min(e, window[1])
                op_time[n] += (hi - lo) * 1e-9 / len(devices)
        mods = clip([(s, e) for _, s, e in _main_program(d["modules"])],
                    window)
        gaps.append(sum(max(0.0, b[0] - a[1])
                        for a, b in zip(mods, mods[1:])))
        coll = union(clip(collective_intervals(ops)
                          + collective_intervals(d.get("async", [])), window))
        if coll:
            rest = [(s, e) for n, s, e in ops if not COLLECTIVE.search(n)]
            xch.append(measure(coll))
            exposed.append(measure(subtract(coll, clip(rest, window))))
    steps = max(n_steps, 1)
    out["busy_s"] = sum(busy) / len(busy) * 1e-9
    out["host_gap_ms"] = sum(gaps) / len(gaps) / steps * 1e-6
    if xch:
        out["exchange_ms"] = max(xch) / steps * 1e-6
        out["exchange_exposed_ms"] = max(exposed) / steps * 1e-6
    out["device_ops"] = [[n, t] for n, t in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = idle_by_host(devices[min(devices)], host, window)
    return out


def _main_program(modules) -> list:
    """Runs of the program that takes the most device time: the step."""
    total = defaultdict(float)
    for n, s, e in modules:
        total[n] += e - s
    if not total:
        return []
    main = max(total, key=total.get)
    return sorted((m for m in modules if m[0] == main), key=lambda m: m[1])


def idle_by_host(device: dict, host: list, window) -> list:
    """Idle device time in the window, split by the host span it fell in
    (``data.next``, ``step.call``, otherwise ``loop.host``)."""
    idle = subtract([window], clip([(s, e) for _, s, e in device["ops"]],
                                   window))
    out, left = [], idle
    for name in HOST_SPANS:
        spans = [(s, e) for n, s, e in host if n == name]
        part = intersect(left, spans)
        out.append([name, measure(part) * 1e-9])
        left = subtract(left, spans)
    out.append([OTHER_HOST, measure(left) * 1e-9])
    return sorted(out, key=lambda kv: -kv[1])


def read_profile(trace_dir) -> tuple:
    """(devices, host spans, window) from the newest trace in ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(str(files[-1]))
    devices, host, window = {}, [], None
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)),
                                   {key: [] for key in LINES.values()})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    d[key].extend((short_name(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return devices, host, window
