"""Device milliseconds of the decode programs per decode iteration, in the
traced window."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    n = len(layers.decode_iters(ctx.plans))
    s = layers.device_s(ctx.trace, layers.DECODE)
    return s / n * 1e3 if n and s else None
