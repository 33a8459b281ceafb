"""Reduce a profiler trace to device busy time, idle share, top device
operations and idle time named by what the host was doing.

Two stages, so the arithmetic can be checked without a chip:

1. ``load_events`` reads an ``.xplane.pb`` into plain lists: each device
   plane's operation events, and the benchmark's host spans (names that
   start with ``fl.``), all in nanoseconds on the trace's clock.
2. ``reduce`` works on those lists alone.
"""
from __future__ import annotations

from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "fl."
TOP = 10


def op_name(text: str) -> str:
    """An XLA op event's HLO text up to its layout: name and result type,
    e.g. ``%fusion.25 = f32[160,24,24,6]``."""
    return text.split("{")[0].strip()


def load_events(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
        "spans": [[name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events)
        else:
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if s >= e:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(spans) -> list[tuple[int, int, str]]:
    """Split nested host spans into disjoint segments, each named by the
    innermost span open in it (the one that started last)."""
    bounds = sorted({t for _, s, d in spans for t in (s, s + d)})
    starts = sorted((s, s + d, name) for name, s, d in spans)
    out, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= a:
            open_.append(starts[i])
            i += 1
        open_ = [x for x in open_ if x[1] > a]
        if open_:
            out.append((a, b, max(open_, key=lambda x: (x[0], -x[1]))[2]))
    return out


def attribute(idle: list[tuple[int, int]], segments) -> dict[str, int]:
    """Idle nanoseconds per innermost host span ("none" outside spans)."""
    named: dict[str, int] = defaultdict(int)
    j = 0
    for s, e in idle:
        covered = 0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                named[name] += ov
                covered += ov
            k += 1
        if e - s > covered:
            named["none"] += e - s - covered
    return dict(named)


def reduce(events: dict, lo: int, hi: int) -> dict:
    """Busy and window seconds (averaged over devices), top device
    operations by total time, idle time by host span, over [lo, hi)."""
    window_s = (hi - lo) / 1e9
    segments = innermost([s for s in events["spans"]
                          if s[1] < hi and s[1] + s[2] > lo])
    busy_s, op_s, idle_named = [], defaultdict(float), defaultdict(float)
    n = len(events["devices"])
    for ops in events["devices"].values():
        inside = [(s, s + d) for _, s, d in ops]
        busy = union(inside, lo, hi)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for name, s, d in ops:
            ov = min(s + d, hi) - max(s, lo)
            if ov > 0:
                op_s[name] += ov / 1e9 / n
        for name, ns in attribute(gaps(busy, lo, hi), segments).items():
            idle_named[name] += ns / 1e9 / n
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(idle_named.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy_s) / n if n else 0.0, "window_s": window_s,
            "devices": n,
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}


def first_span(events: dict, name: str) -> tuple[int, int]:
    """[start, end) of the first host span called ``name``."""
    for n, s, d in events["spans"]:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name!r} span in the trace")
