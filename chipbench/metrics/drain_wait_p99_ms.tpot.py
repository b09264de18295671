"""99th percentile of single ``engine.drain_wait`` spans in the traced
window: the host blocked in the one ``jax.device_get`` that materializes
sampled tokens (``ServingEngine._drain_tokens``), in ms."""
from chipbench import program_trace, stats


def read(ctx):
    p = program_trace.of(ctx)
    d = p and p.durations.get("engine.drain_wait")
    return stats.percentile(d, 99) * 1e3 if d else None
