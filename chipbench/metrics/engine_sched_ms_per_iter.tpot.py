"""Host milliseconds per call of the engine's own ``engine.form_batch``
span around ``scheduler.form_batch``, in the traced window: the inside
twin of ``sched_ms_per_iter.tpot``, which times the harness's wrapper (the
program span holds that wrapper and its plan record)."""
from chipbench import program_trace


def read(ctx):
    p = program_trace.of(ctx)
    d = p and p.durations.get("engine.form_batch")
    return sum(d) / len(d) * 1e3 if d else None
