"""Reduction of a profiler trace to the benchmark's numbers.

A trace is read with ``jax.profiler.ProfileData``, from the profiler
session itself or from a ``.xplane.pb`` file.  Each TPU device is a plane
named ``/device:TPU:<n>``: its ``XLA Ops`` line holds one event per
operation (a ``while`` loop's event spans the events of its body), its
``Async XLA Ops`` line the time async operations are in flight, its
``XLA Modules`` line one event per program run.  An operation's event name
is its HLO text; only the instruction name before `` = `` is kept, and the
names of collective instructions, found by their opcode (the compiler names
an all-reduce after its source, ``psum.211``), are kept apart.  Host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines, on
the same clock.  Interval arithmetic works on (start, end) pairs in
nanoseconds; an ``XLA Ops`` line, millions of events in a window, is held
as columns (``Timeline``) and reduced with numpy.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
LINES = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(           # searched in an instruction's opcode
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
ASYNC_END = re.compile(r"-(start|done)(?=[.\d]|$)")
# the opcode of an HLO instruction's text: the first lower-case word
# followed by "(" after " = " (layouts' T(..) and S(..) are upper case)
OPCODE = re.compile(r" = .*?(?<![\w.%])([a-z][a-z0-9-]*)\(")
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
    """The parts of ``a`` that ``b`` does not cover, in one sweep over
    both sorted covers."""
    out, b, j = [], union(b), 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:      # ends before all of a's rest
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
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


def opcode(hlo_text: str) -> str:
    """``%psum.2 = f32[8]{0} all-reduce(f32[8]{0} %x), ...`` ->
    ``all-reduce``; empty where the text is no instruction."""
    m = OPCODE.search(hlo_text)
    return m.group(1) if m else ""


class Timeline(NamedTuple):
    """A line's events as columns: event ``i`` is ``names[ids[i]]``, from
    ``start[i]`` to ``end[i]`` (ns)."""
    names: list
    ids: np.ndarray
    start: np.ndarray
    end: np.ndarray

    def events(self) -> list:
        return [(self.names[i], s, e) for i, s, e in zip(
            self.ids.tolist(), self.start.tolist(), self.end.tolist())]


def timeline(line) -> Timeline:
    """``line``, a Timeline or a list of (name, start, end), as a
    Timeline."""
    if isinstance(line, Timeline):
        return line
    index = {}
    ids = [index.setdefault(n, len(index)) for n, _, _ in line]
    return Timeline(list(index), np.asarray(ids, np.int64),
                    np.asarray([s for _, s, _ in line], np.float64),
                    np.asarray([e for _, _, e in line], np.float64))


def events(line) -> list:
    """A line as a list of (name, start, end)."""
    return line.events() if isinstance(line, Timeline) else line


def _union(start, end) -> tuple:
    """``union`` of the intervals given as two arrays, as two arrays."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, reach = start[order], np.maximum.accumulate(end[order])
    first = np.ones(start.size, bool)
    first[1:] = start[1:] > reach[:-1]
    last = np.append(np.flatnonzero(first)[1:] - 1, start.size - 1)
    return start[first], reach[last]


def _covered(start, end, intervals) -> float:
    """Length of the sorted, disjoint cover (``start``, ``end``) inside the
    disjoint ``intervals``."""
    if start.size == 0 or not intervals:
        return 0.0
    below = np.append(0.0, np.cumsum(end - start))

    def upto(x):                    # length of the cover before x
        k = np.searchsorted(end, x, side="right")
        part = np.maximum(0.0, x - start[np.minimum(k, start.size - 1)])
        return below[k] + np.where(k < start.size, part, 0.0)

    lo, hi = np.asarray(intervals, np.float64).T
    return float(np.sum(upto(hi) - upto(lo)))


def collective_intervals(ops, collectives) -> list:
    """Time a collective is in flight: a synchronous collective op's own
    interval, or an async pair's span from its ``-start`` to its ``-done``.
    ``collectives``: the names of collective ops."""
    out, open_starts = [], defaultdict(list)
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        if name not in collectives:
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

    ``devices``: {index: {"ops": ..., "async": ..., "modules": ...,
    "collectives": {name}}}, each line a Timeline or a list of (name,
    start, end), names short, ``collectives`` the names of collective ops;
    ``host``: [(span name, start, end)]; ``window``: (start, end) of the
    measured window on the same clock; ``n_steps``: steps in the window.
    """
    lo, hi = window
    out = {"window_s": (hi - lo) * 1e-9, "n_devices": len(devices)}
    if not devices:
        out.update(busy_s=0.0, device_ops=[], idle_gaps=[])
        return out
    busy, gaps, xch, exposed = [], [], [], []
    op_time = defaultdict(float)
    for d in devices.values():
        ops = timeline(d["ops"])
        inside, s, e = _inside(ops, window)
        ids = ops.ids[inside]
        busy_start, busy_end = _union(s, e)
        busy.append(float(np.sum(busy_end - busy_start)))
        named = d.get("collectives", frozenset())
        work = ~_flags(ops.names, CONTAINER.match)[ids]
        collective = _flags(ops.names, named.__contains__)[ids]
        per_name = np.bincount(ids[work], weights=(e - s)[work],
                               minlength=len(ops.names))
        for i in np.flatnonzero(per_name):
            op_time[ops.names[i]] += float(per_name[i])
        mods = clip([(s_, e_) for _, s_, e_ in
                     _main_program(events(d["modules"]))], window)
        gaps.append(sum(max(0.0, b[0] - a[1])
                        for a, b in zip(mods, mods[1:])))
        in_flight = [(ops.names[i], s_, e_) for i, s_, e_ in zip(
            ids[collective].tolist(), ops.start[inside][collective].tolist(),
            ops.end[inside][collective].tolist())]
        coll = union(clip(collective_intervals(in_flight, named)
                          + collective_intervals(events(d.get("async", [])),
                                                 named), window))
        if coll:
            rest = work & ~collective
            xch.append(measure(coll))
            exposed.append(xch[-1] - _covered(*_union(s[rest], e[rest]),
                                              coll))
    steps = max(n_steps, 1)
    out["busy_s"] = sum(busy) / len(busy) * 1e-9
    out["host_gap_ms"] = sum(gaps) / len(gaps) / steps * 1e-6
    if xch:
        out["exchange_ms"] = max(xch) / steps * 1e-6
        out["exchange_exposed_ms"] = max(exposed) / steps * 1e-6
    out["device_ops"] = [[n, t * 1e-9 / len(devices)] for n, t in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = idle_by_host(devices[min(devices)], host, window)
    return out


def _inside(ops: Timeline, window) -> tuple:
    """Which ops run in ``window``, and their starts and ends clipped to
    it."""
    lo, hi = window
    inside = (ops.end > lo) & (ops.start < hi)
    return (inside, np.maximum(ops.start[inside], lo),
            np.minimum(ops.end[inside], hi))


def _flags(names, test) -> np.ndarray:
    return np.array([bool(test(n)) for n in names], bool)


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
    _, start, end = _inside(timeline(device["ops"]), window)
    start, end = _union(start, end)
    idle_start, idle_end = np.append(window[0], end), \
        np.append(start, window[1])
    keep = idle_end > idle_start
    idle_start, idle_end = idle_start[keep], idle_end[keep]
    parts, seen = {}, []
    for name in HOST_SPANS:
        spans = [(s, e) for n, s, e in host if n == name]
        parts[name] = _covered(idle_start, idle_end, subtract(spans, seen))
        seen += spans
    parts[OTHER_HOST] = float(np.sum(idle_end - idle_start)) \
        - sum(parts.values())
    return sorted(([n, v * 1e-9] for n, v in parts.items()),
                  key=lambda kv: -kv[1])


def read_xspace(prof) -> tuple:
    """(devices, host spans, window) from a ``ProfileData``.  A distinct
    HLO text is named and classified once, for all devices."""
    lines, host, window = {}, [], None
    short, names, collectives = {}, {}, set()    # HLO text -> id; name -> id
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = lines.setdefault(int(m.group(1)), {
                key: ([], [], []) for key in LINES.values()})
            for line in plane.lines:
                key = LINES.get(line.name)
                if not key:
                    continue
                ids, start, length = dev[key]
                for e in line.events:
                    i = short.get(e.name)
                    if i is None:
                        name = short_name(e.name)
                        i = short[e.name] = names.setdefault(name, len(names))
                        if key != "modules" and COLLECTIVE.search(
                                opcode(e.name)):
                            collectives.add(name)
                    ids.append(i)
                    start.append(e.start_ns)
                    length.append(e.duration_ns)
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
    names = list(names)
    devices = {}
    for index, dev in lines.items():
        devices[index] = d = {"collectives": collectives}
        for key, (ids, start, length) in dev.items():
            start = np.asarray(start, np.float64)
            d[key] = Timeline(names, np.asarray(ids, np.int64), start,
                              start + np.asarray(length, np.float64))
    return devices, host, window

