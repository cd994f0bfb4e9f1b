"""Reduction of a JAX profiler trace (`.xplane.pb`) to the device's
busy time, each operation's device time, and the device's idle gaps
labelled by what the host was doing.

The profiler's clock is not the host clock the harness keeps its spans
on.  The harness enters each of its `step` spans as a
`jax.profiler.TraceAnnotation` too, so the trace holds the same spans
on its own clock; the median offset between the two copies maps every
device event onto the host clock.
"""
from __future__ import annotations

import bisect
from typing import Iterable, List, Tuple

import numpy as np

Span = Tuple[str, float, float]   # (name, start, end), host clock, s

#: the line of a device plane that holds one event per operation run
OPS_LINE = "XLA Ops"


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(prof) -> list:
    return [p for p in prof.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def device_ops(prof) -> List[Tuple[str, float, float]]:
    """Every operation on every device, (name, start_ns, end_ns) on the
    profiler's clock."""
    out = []
    for plane in device_planes(prof):
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def host_events(prof, name: str) -> List[Tuple[float, float]]:
    """Host events of one name (the harness's annotations), sorted."""
    out = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(out)


def clock_offset_ns(prof, spans: Iterable[Span],
                    anchor: str = "step") -> float:
    """profiler ns minus host-clock ns, from the anchor spans."""
    mine = sorted(s for n, s, _ in spans if n == anchor)
    theirs = host_events(prof, anchor)
    k = min(len(mine), len(theirs))
    if k == 0:
        raise ValueError(f"no {anchor!r} annotation in the trace")
    return float(np.median([theirs[i][0] - mine[i] * 1e9
                            for i in range(k)]))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class SpanIndex:
    """Host spans sorted by start, to find those overlapping a gap."""

    def __init__(self, spans: List[Span]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] - s[1] for s in self.spans), default=0.0)

    def split(self, a: float, b: float) -> dict:
        """Seconds of [a, b) under each span, each instant given to the
        innermost (shortest) span holding it, or to "outside spans"."""
        lo = bisect.bisect_left(self.starts, a - self.longest)
        hi = bisect.bisect_left(self.starts, b)
        inside = sorted((s for s in self.spans[lo:hi] if s[2] > a),
                        key=lambda s: s[2] - s[1])
        cuts = sorted({a, b} | {t for s in inside for t in (s[1], s[2])
                                if a < t < b})
        out = {}
        for c0, c1 in zip(cuts[:-1], cuts[1:]):
            mid = (c0 + c1) / 2
            lab = next((s[0] for s in inside if s[1] <= mid < s[2]),
                       "outside spans")
            out[lab] = out.get(lab, 0.0) + (c1 - c0)
        return out


def op_name(name: str) -> str:
    """An HLO operation's instruction name, without its signature."""
    return name.split(" = ", 1)[0]


def reduce(prof, t0: float, t1: float, spans: List[Span],
           n_devices: int = 1) -> dict:
    """Busy time, device time per operation (by HLO instruction name)
    and idle time by host span, over the stretch [t0, t1) (host clock,
    s).  Busy time is averaged over `n_devices`."""
    off = clock_offset_ns(prof, spans)
    ops = [(name, (a - off) * 1e-9, (b - off) * 1e-9)
           for name, a, b in device_ops(prof)]
    ops = [(name, max(a, t0), min(b, t1)) for name, a, b in ops
           if b > t0 and a < t1]
    busy_iv = union([(a, b) for _, a, b in ops])
    busy = sum(b - a for a, b in busy_iv) / max(n_devices, 1)
    by_op = {}
    for name, a, b in ops:
        key = op_name(name)
        by_op[key] = by_op.get(key, 0.0) + (b - a)
    gaps, prev = [], t0
    for a, b in busy_iv:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if prev < t1:
        gaps.append((prev, t1))
    idle = {}
    index = SpanIndex(spans)
    for a, b in gaps:
        for lab, sec in index.split(a, b).items():
            idle[lab] = idle.get(lab, 0.0) + sec
    return {"busy_s": busy, "window_s": t1 - t0, "by_op": by_op,
            "idle_by_span": idle}


def top(d: dict, k: int = 10) -> list:
    return [[name, float(v)] for name, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
