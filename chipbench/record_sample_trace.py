"""Record the small device trace that ``tests/bench`` checks the trace
reduction against.

Run on a TPU host from the checkout root:

    python3 -m chipbench.record_sample_trace <output directory>

It runs a jitted matmul chain a few times inside host spans named like the
harness's (``step``, ``form_batch``, ``_drain_tokens``), with host sleeps
that leave the device idle inside known spans, and prints where the
profiler wrote its ``.xplane.pb`` and the plane and line names it holds.
"""
from __future__ import annotations

import glob
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(argv) -> int:
    out = argv[1] if len(argv) > 1 else "sample_trace"
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2

    @jax.jit
    def chain(a, b):
        for _ in range(4):
            a = jnp.tanh(a @ b)
        return a

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    chain(a, b).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("form_batch"):
                time.sleep(0.02)
            a = chain(a, b)
            with jax.profiler.TraceAnnotation("_drain_tokens"):
                a.block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    for path in glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                          recursive=True):
        print("xplane", path, os.path.getsize(path))
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            lines = list(plane.lines)
            print("plane", repr(plane.name), [l.name for l in lines])
            for line in lines:
                evs = list(line.events)
                for ev in evs[:3]:
                    print("  ", repr(line.name), repr(ev.name), ev.start_ns,
                          ev.duration_ns, dict(ev.stats) if hasattr(
                              ev, "stats") else None)
                print("  ", repr(line.name), "events", len(evs))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
