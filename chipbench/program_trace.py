"""The engine's own spans in a traced run, read from the ``.xplane.pb``
that ``trace_reduce`` reduces.

``repro.obs.spans`` writes them onto the host plane while the profiler
runs: ``engine.admit``, ``engine.form_batch``, ``engine.prefill``,
``engine.decode``, ``engine.drain_wait`` and ``engine.finish``. From the
traced window this reads:

- the duration of each such span that starts in the window;
- the run-ahead counters that each ``engine.decode`` span carries as stats
  (``ahead_iters``, ``ahead_samples``: the engine's running sums as they
  stood when the span opened), as their change from the first to the last
  decode span of the window;
- ``idle_gaps_program``: the device's idle gaps, each piece charged to the
  innermost span open, program and harness spans together, so that a gap
  inside a harness span that holds no program span still goes to the
  harness span.

A program without these spans gives empty durations, no run-ahead and the
harness-only attribution; the metric readers then return None.
"""
from __future__ import annotations

import functools
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace_reduce as tr

PREFIX = "engine."
DECODE = "engine.decode"


@dataclass
class ProgramSpans:
    """Seconds, over the traced window."""
    durations: Dict[str, List[float]]
    run_ahead: Optional[Tuple[float, float]]    # (iterations, dispatches)
    idle_by_span: Dict[str, float]

    @property
    def run_ahead_iters(self) -> Optional[float]:
        """Mean undrained decode iterations at a dispatch."""
        if not self.run_ahead or not self.run_ahead[1]:
            return None
        return self.run_ahead[0] / self.run_ahead[1]


def idle_by_span(spans: Sequence[Tuple[float, float, str]],
                 busy_by_device: Sequence[Sequence[tr.Interval]],
                 lo: float, hi: float) -> Dict[str, float]:
    """Idle time in [lo, hi) of each device (the complement of its busy
    intervals), each piece charged to the innermost of the properly nested
    ``spans`` open in it, averaged over the devices."""
    host = tr.Spans(spans)
    idle: Dict[str, float] = defaultdict(float)
    for busy in busy_by_device:
        for k, v in host.attribute(tr.complement(busy, lo, hi)).items():
            idle[k] += v / len(busy_by_device)
    return dict(idle)


def reduce(path: str, window: Tuple[float, float],
           harness_spans: Sequence[str]) -> ProgramSpans:
    """Read the engine spans of the trace at ``path`` (a file or a
    directory holding one) inside ``window`` (seconds on the trace's
    clock, as ``trace_reduce.Reduced.window`` gives it)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    pd = ProfileData.from_file(path)
    lo, hi = window
    harness = set(harness_spans)
    spans: List[Tuple[float, float, str]] = []
    decode: List[Tuple[float, dict]] = []
    busy_by_device: List[List[tr.Interval]] = []
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            ops = [(e.start_ns / 1e9, e.end_ns / 1e9) for line in plane.lines
                   if line.name == tr.OPS_LINE for e in line.events]
            busy_by_device.append(tr.union(tr.clip(ops, lo, hi)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if not (name.startswith(PREFIX) or name in harness):
                        continue
                    a = e.start_ns / 1e9
                    spans.append((a, e.end_ns / 1e9, name))
                    if name == DECODE and lo <= a < hi:
                        decode.append((a, dict(e.stats)))
    durations: Dict[str, List[float]] = defaultdict(list)
    for a, b, name in spans:
        if name.startswith(PREFIX) and lo <= a < hi:
            durations[name].append(b - a)
    counted = sorted((d for d in decode
                      if "ahead_iters" in d[1] and "ahead_samples" in d[1]),
                     key=lambda d: d[0])
    run_ahead = None
    if len(counted) >= 2:
        first, last = counted[0][1], counted[-1][1]
        run_ahead = (float(last["ahead_iters"] - first["ahead_iters"]),
                     float(last["ahead_samples"] - first["ahead_samples"]))
    return ProgramSpans(dict(durations), run_ahead,
                        idle_by_span(spans, busy_by_device, lo, hi))


@functools.lru_cache(maxsize=1)
def _cached(path: str, mtime: float, window: Tuple[float, float],
            harness_spans: Tuple[str, ...]) -> ProgramSpans:
    return reduce(path, window, harness_spans)


def of(ctx) -> Optional[ProgramSpans]:
    """The engine spans of a traced benchmark run (read once for all the
    metric readers of the run); None for an untraced one."""
    if ctx.trace is None:
        return None
    from chipbench import run
    path = tr.find_xplane(run.TRACE_DIR)
    return _cached(path, os.path.getmtime(path), tuple(ctx.trace.window),
                   tuple(run.SPANS))
