"""Named host spans of the serving engine on the JAX profiler's timeline.

    from repro.obs.spans import span
    with span("engine.form_batch"):
        plan = scheduler.form_batch(now)

While a profiler session collects (``jax.profiler.trace`` /
``start_trace``) and the module switch ``enabled`` is on, ``span`` is a
``jax.profiler.TraceAnnotation``: the span lands on the host plane of the
same ``.xplane.pb`` as the device ops, on the device trace's clock, and
its keyword arguments ride along as the event's stats. Otherwise it is one
shared no-op context, so an untraced engine pays a flag test and a native
call per span and allocates nothing.

Names are ``engine.<phase>``, one per call site of ``ServingEngine.step``
and its decode path (PERF.md lists the metric that reads each).
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

__all__ = ["span", "enabled"]

_OFF = contextlib.nullcontext()

# Set to False to keep engine spans out of a trace that is being taken.
enabled = True


def span(name: str, **stats):
    """A host span named ``name`` while spans are on and a profiler
    session collects; a shared no-op context otherwise."""
    if enabled and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **stats)
    return _OFF
