"""Share of the roofline reached by the decode programs: the least time
of the traced decode iterations' work (``work.decode_iteration``: weights
once per iteration, live-context K/V) over their device time, in %."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    _, least, n = layers.decode_work(ctx)
    s = layers.device_s(ctx.trace, layers.DECODE)
    return least / s * 100.0 if n and s else None
