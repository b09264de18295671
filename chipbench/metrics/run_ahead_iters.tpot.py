"""Decode iterations dispatched but not yet drained to the host, averaged
over the async decode dispatches of the traced window: the change of the
engine's ``ahead_iters_sum`` over that of ``n_ahead_samples``, as the
``engine.decode`` spans carry them."""
from chipbench import program_trace


def read(ctx):
    p = program_trace.of(ctx)
    return p.run_ahead_iters if p else None
