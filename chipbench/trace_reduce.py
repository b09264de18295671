"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

- the traced window: the host span named ``window`` that the harness opens
  around the traced part of its measured window (or, failing that, the
  extent of the device events);
- device busy time: the union of the intervals in which an XLA operation
  ran on each device (line ``XLA Ops`` of a ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the devices;
- device time per operation name (its own time, less the operations
  nested in it, as a ``while`` holds its body) and per program (line
  ``XLA Modules``);
- idle gaps (the complement of busy within the window), each attributed
  to the innermost host span open at the time, or ``host_other`` where no
  named span was open.

Host spans are the ``jax.profiler.TraceAnnotation`` events on the host
plane; their names are the harness's (``submit``, ``step``, ...).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
OTHER = "host_other"


@dataclass
class Reduced:
    """Everything in seconds."""
    window: Interval
    n_devices: int
    busy_s: float
    op_s: Dict[str, float]
    module_s: Dict[str, float]
    module_calls: Dict[str, int]
    idle_by_span: Dict[str, float]
    span_s: Dict[str, float]
    span_n: Dict[str, int]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


class Spans:
    """Properly nested host spans of one thread, queried for the innermost
    span open at a time."""

    def __init__(self, spans: Iterable[Tuple[float, float, str]]):
        self.spans = sorted(spans, key=lambda s: (s[0], -s[1]))
        self.starts = [s[0] for s in self.spans]
        depth, stack = [], []
        for a, b, _ in self.spans:
            while stack and stack[-1] <= a:
                stack.pop()
            depth.append(len(stack))
            stack.append(b)
        self.depth = depth
        self.bounds = sorted({t for a, b, _ in self.spans for t in (a, b)})

    def innermost(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            a, b, name = self.spans[i]
            if b > t:
                return name
            if self.depth[i] == 0:
                break
            i -= 1
        return OTHER

    def attribute(self, gaps: Sequence[Interval]) -> Dict[str, float]:
        """Split each gap at span boundaries and charge each piece to the
        innermost span open in it."""
        out: Dict[str, float] = defaultdict(float)
        for a, b in gaps:
            cuts = self.bounds[bisect.bisect_right(self.bounds, a):
                               bisect.bisect_left(self.bounds, b)]
            edges = [a] + cuts + [b]
            for x, y in zip(edges, edges[1:]):
                if y > x:
                    out[self.innermost((x + y) / 2.0)] += y - x
        return dict(out)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def clip_events(events, lo: float, hi: float):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def self_times(events):
    """(name, own time) of each event: its duration less that of the
    events nested in it (a ``while`` op holds its body's ops)."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [b - a for _, a, b in events]
    stack: List[int] = []
    for i, (_, a, b) in enumerate(events):
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(e[0], t) for e, t in zip(events, own)]


def _events(line):
    for ev in line.events:
        a = float(ev.start_ns)
        yield ev.name, a, a + float(ev.duration_ns)


def reduce(path: str, span_names: Sequence[str],
           window: Optional[Interval] = None) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb`` file or a directory
    holding one). Times are the profiler's nanoseconds, returned as
    seconds."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    names = set(span_names) | {WINDOW_SPAN}
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = list(_events(line))
                elif line.name == MODULES_LINE:
                    mods = list(_events(line))
            devices.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in ((a, b, n) for n, a, b in _events(line))
                          if e[2] in names]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane")
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if window is not None:
        lo, hi = window[0] * 1e9, window[1] * 1e9
    elif win:
        lo, hi = min(s[0] for s in win), max(s[1] for s in win)
    else:
        evs = [e for ops, mods in devices for e in ops + mods]
        lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    host = Spans(s for s in spans if s[2] != WINDOW_SPAN)
    busy_total = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    for ops, mods in devices:
        busy = union(clip(((a, b) for _, a, b in ops), lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for name, dt in self_times(clip_events(ops, lo, hi)):
            op_s[op_name(name)] += dt / 1e9
        for name, a, b in mods:
            if lo <= a < hi:
                module_s[name] += (b - a) / 1e9
                module_calls[name] += 1
        gaps = complement(busy, lo, hi)
        for k, v in host.attribute(gaps).items():
            idle[k] += v / 1e9 / len(devices)
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    for a, b, name in host.spans:
        if lo <= a < hi:
            span_s[name] += (b - a) / 1e9
            span_n[name] += 1
    n = len(devices)
    return Reduced(window=(lo / 1e9, hi / 1e9), n_devices=n,
                   busy_s=busy_total / n / 1e9,
                   op_s={k: v / n for k, v in op_s.items()},
                   module_s={k: v / n for k, v in module_s.items()},
                   module_calls=dict(module_calls), idle_by_span=dict(idle),
                   span_s=dict(span_s), span_n=dict(span_n))


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
