"""A training run's profiler trace, reduced by what the program marks.

``train_loop`` marks each iteration with a ``train.step`` span holding
``train.data``, ``train.dispatch``, ``train.sync`` and ``train.checkpoint``;
the BERT step's ops carry the named scopes ``bert.embed``,
``bert.attention``, ``bert.ffn``, ``bert.heads`` and ``lamb`` in their op
names.  This module splits the device's idle time in a window by the
innermost of those spans, and its busy time by the innermost scope.  It
reads the trace as ``bench/trace.py`` does and uses its interval
arithmetic; what ``bench/trace.py`` returns is left as it is.

  python3 bench/program.py <trace dir>

reduces the newest trace written under ``<trace dir>`` by
``jax.profiler.trace(<trace dir>)`` around a ``train_loop`` call, and
prints the result as JSON.
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

if __package__ in (None, ""):       # run as a script: import bench as a
    sys.path[0] = str(Path(__file__).resolve().parent.parent)   # package

from bench import trace  # noqa: E402

STEP_SPAN = "train.step"
CHILD_SPANS = ("train.data", "train.dispatch", "train.sync",
               "train.checkpoint")
LOOP, OUTSIDE, OTHER = "loop", "outside", "other"
SCOPES = ("bert.embed", "bert.attention", "bert.ffn", "bert.heads", "lamb")
SCOPE = re.compile(r"(?<![\w.])(bert\.(?:embed|attention|ffn|heads)|lamb)"
                   r"(?![\w.])")
OP_NAME_STAT = "tf_op"     # "<op name>:<op type>", on TPU op metadata


def scope_of(op_name: str) -> str:
    """The innermost program scope named in an op name, else ``other``:
    ``.../transpose(jvp(bert.ffn))/dot_general`` -> ``bert.ffn``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else OTHER


def _segments(host) -> list:
    """Sorted, disjoint (start, end, part): each child span, and the rest of
    each ``train.step`` as ``loop``.  The children never overlap: the loop
    runs them in turn."""
    kids = [(s, e, n) for n, s, e in host if n in CHILD_SPANS]
    steps = [(s, e) for n, s, e in host if n == STEP_SPAN]
    rest = trace.subtract(steps, [(s, e) for s, e, _ in kids])
    return sorted(kids + [(s, e, LOOP) for s, e in rest])


def split_idle(device_ops, host, window) -> dict:
    """Idle device time in ``window`` (ns), by the innermost program span
    it fell in: each of ``CHILD_SPANS``, else ``loop`` inside a
    ``train.step``, else ``outside``.  One sweep over the idle intervals;
    the parts sum to the idle time."""
    idle = trace.subtract([window], trace.clip(
        [(s, e) for _, s, e in device_ops], window))
    segs = _segments(host)
    out = dict.fromkeys(CHILD_SPANS + (LOOP,), 0)
    j = 0
    for s, e in idle:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            lo, hi, part = segs[k]
            out[part] += max(0, min(e, hi) - max(s, lo))
            k += 1
    out[OUTSIDE] = trace.measure(idle) - sum(out.values())
    return out


def split_busy(device_ops, op_names: dict, window) -> dict:
    """Device time in ``window`` (ns) of every op but loops and calls, by
    the innermost scope in its op name; the scope of a name is found once."""
    out = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    scope = {}
    for name, s, e in device_ops:
        if e <= window[0] or s >= window[1] or trace.CONTAINER.match(name):
            continue
        if name not in scope:
            scope[name] = scope_of(op_names.get(name, ""))
        out[scope[name]] += min(e, window[1]) - max(s, window[0])
    return out


def reduce_program(devices: dict, host: list, window, n_steps: int
                   ) -> dict:
    """The program's split of one traced window, per step in ms.

    Arguments as ``trace.reduce_timelines`` takes them; a device may also
    hold ``op_names`` ({short op name: op name}).  ``idle_ms``: the first
    device's idle time by span (``split_idle``); ``busy_ms``: device time
    by scope (``split_busy``), averaged over devices.
    """
    per_step = 1e-6 / max(n_steps, 1)
    out = {"n_steps": n_steps, "idle_ms": {}, "busy_ms": {}}
    if not devices:
        return out
    idle = split_idle(devices[min(devices)]["ops"], host, window)
    busy = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    for d in devices.values():
        for k, v in split_busy(d["ops"], d.get("op_names", {}),
                               window).items():
            busy[k] += v / len(devices)
    out["idle_ms"] = {k: v * per_step for k, v in idle.items()}
    out["busy_ms"] = {k: v * per_step for k, v in busy.items()}
    return out


def metrics(program: dict) -> dict:
    """The per-layer numbers a benchmark line would carry, in ms/step."""
    idle, busy = program["idle_ms"], program["busy_ms"]
    if not idle:
        return {}
    return {"idle_data_ms": idle["train.data"],
            "idle_dispatch_ms": idle["train.dispatch"],
            "idle_sync_ms": idle["train.sync"], "idle_loop_ms": idle[LOOP],
            "attention_ms": busy["bert.attention"],
            "ffn_ms": busy["bert.ffn"], "lamb_ms": busy["lamb"]}


def _op_name(event, stats: dict) -> str:
    """The op name (stat ``tf_op``) an op event carries, in its own stats
    or in its metadata's (``stats``, by event name); empty if none."""
    own = dict(event.stats).get(OP_NAME_STAT)
    return own or stats.get(event.name, {}).get(OP_NAME_STAT, "")


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int,
    or the bytes of a length-delimited field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:                                   # fixed 64 or 32 bits
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def metadata_stats(xspace: bytes) -> dict:
    """{device plane name: {event name: {stat name: string value}}} from a
    serialized XSpace: the stats an event's metadata holds, which
    ``ProfileData`` does not show.  The planes' event lines are skipped
    unread, so this costs a pass over a few thousand op descriptions."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:                          # XSpace.planes
            continue
        parts = {2: [], 4: [], 5: []}           # name, event and stat metadata
        for f, v in _fields(plane):
            if f in parts:
                parts[f].append(v)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for entry in parts[5]:                  # map<int64, XStatMetadata>
            meta = dict(_fields(entry)).get(2, b"")
            sm = dict(_fields(meta))
            stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        events = out.setdefault(name, {})
        for entry in parts[4]:                  # map<int64, XEventMetadata>
            meta = dict(_fields(entry)).get(2, b"")
            ev_name, stats = "", {}
            for f, v in _fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:                    # XStat
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1, 0), "")
                    if 5 in st:                 # str_value
                        stats[key] = bytes(st[5]).decode()
                    elif 7 in st:               # ref_value: a stat name
                        stats[key] = stat_names.get(st[7], "")
            if stats:
                events[ev_name] = stats
    return out


def read_program(trace_dir) -> tuple:
    """(devices, host spans, window) from the newest trace in
    ``trace_dir``, in ``trace.read_profile``'s form, with the program's
    ``train.*`` spans among the host spans and each device's
    ``op_names``.  The window is the ``bench.window`` span, else the
    stretch from the first ``train.step`` to the end of the last."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    raw = files[-1].read_bytes()
    prof = ProfileData.from_serialized_xspace(raw)
    meta = metadata_stats(raw)
    devices, host, window = {}, [], None
    for plane in prof.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), {
                key: [] for key in trace.LINES.values()})
            d["op_names"] = {}
            for line in plane.lines:
                key = trace.LINES.get(line.name)
                if not key:
                    continue
                names = {}      # the event's own name -> its short name
                for e in line.events:
                    name = names.get(e.name)
                    if name is None:
                        name = names[e.name] = trace.short_name(e.name)
                        if key == "ops":
                            d["op_names"].setdefault(name, _op_name(
                                e, meta.get(plane.name, {})))
                    d[key].append((name, e.start_ns,
                                   e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith("train.") or \
                            e.name in trace.HOST_SPANS:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    if window is None:
        steps = [(s, e) for n, s, e in host if n == STEP_SPAN]
        if not steps:
            raise ValueError(f"no {trace.WINDOW_SPAN!r} or {STEP_SPAN!r} "
                             "span in the trace")
        window = (min(s for s, _ in steps), max(e for _, e in steps))
    return devices, host, window


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 bench/program.py <trace dir>", file=sys.stderr)
        return 2
    devices, host, window = read_program(args[0])
    n_steps = sum(1 for n, s, e in host if n == STEP_SPAN
                  and s >= window[0] and e <= window[1])
    out = reduce_program(devices, host, window, n_steps)
    out["metrics"] = metrics(out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
