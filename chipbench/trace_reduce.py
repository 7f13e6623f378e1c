"""From a profiler trace to the device's busy time, its busiest operations
and its idle gaps, each gap named by what the host was doing.

The trace is the ``.xplane.pb`` file that ``jax.profiler`` writes; it is
read with ``jax.profiler.ProfileData``, nothing else. Device planes are those
named ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
operation that ran, named by its HLO text (``%fusion.12 = f32[...] ...``).
A loop's event spans the events of its body, so an operation's time is its
self time: its duration less that of the events nested in it. Host spans
are the ``TraceAnnotation`` events on the host plane; the span named
``window`` bounds what is reduced.

    python chipbench/trace_reduce.py <file.xplane.pb>
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
HLO_TEXT = re.compile(r"%?(\S+) = \(?(\w+\[[^\]]*\])")


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_seconds(intervals) -> float:
    return sum(e - s for s, e in _union(intervals))


def _self_times(ops):
    """[(name, self seconds)] of one line's (name, start, end) events: each
    event's duration less that of the events nested in it."""
    out, stack = [], []             # open events: [name, start, end, nested]

    def close():
        name, s, e, nested = stack.pop()
        out.append((name, (e - s - nested) / 1e9))
        if stack:
            stack[-1][3] += e - s
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] < max(e, s + 1):
            close()
        stack.append([name, s, e, 0])
    while stack:
        close()
    return out


def _short(name: str) -> str:
    """The operation's name and its (first) result's shape:
    ``%fusion.12 = (f32[4,64]{1,0}, ...) fusion(...)`` -> ``fusion.12
    f32[4,64]``."""
    m = HLO_TEXT.match(name)
    return f"{m[1]} {m[2]}" if m else name


def read_events(path):
    """(device ops per device plane, host spans), times in nanoseconds:
    ({plane: [(name, start, end)]}, [(name, start, end)])."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return devices, host


def reduce(path, span_names, top: int = 10) -> dict:
    """Busy and window seconds, the ``top`` operations by total device self
    time and the idle time by the innermost of ``span_names`` that covers
    it.

    Busy time, each operation's time and the idle time are averaged over
    the device planes that ran an operation in the window, so that each
    reads per chip. Idle time with no such span over it is named
    ``other``."""
    devices, host = read_events(path)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    cuts, labels = _segments([(s, e, n) for n, s, e in host
                              if n in span_names and e > lo and s < hi])
    op_time = defaultdict(float)
    idle = defaultdict(float)
    busy = []
    for ops in devices.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                   if e > lo and s < hi]
        if not clipped:
            continue
        for n, sec in _self_times(clipped):
            op_time[_short(n)] += sec
        merged = _union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                for name, sec in _name_gap(gs, ge, cuts, labels):
                    idle[name] += sec / 1e9
    if not busy:
        raise ValueError(f"{path}: no device operation in the window")
    op_time = {k: v / len(busy) for k, v in op_time.items()}
    idle = {k: v / len(busy) for k, v in idle.items()}
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "n_devices": len(busy),
            "device_ops": rank(op_time),
            "idle_gaps": rank(idle)}


def _segments(spans):
    """Cut the time line at every span edge: (cuts, labels), where
    labels[i] names the innermost span over [cuts[i], cuts[i+1]), or
    ``other`` where none is."""
    cuts = sorted({x for s, e, _ in spans for x in (s, e)})
    starts = defaultdict(list)
    for s, e, n in spans:
        starts[s].append((e - s, e, n))
    active, labels = [], []
    for x in cuts:
        active = [a for a in active if a[1] > x] + starts.get(x, [])
        labels.append(min(active)[2] if active else "other")
    return cuts, labels


def _name_gap(gs, ge, cuts, labels):
    """Split the gap [gs, ge) by the segments it crosses: yields
    (name, nanoseconds)."""
    i = bisect.bisect_right(cuts, gs) - 1
    at = gs
    while at < ge:
        nxt = cuts[i + 1] if i + 1 < len(cuts) else ge
        end = min(ge, nxt)
        yield (labels[i] if i >= 0 else "other"), end - at
        at, i = end, i + 1


if __name__ == "__main__":
    out = reduce(sys.argv[1], set(sys.argv[2:]) or {"train_step"})
    print(json.dumps(out, indent=1))
