"""Device milliseconds of the prefill programs (packed and chunked, with
their cache seeding) per 1000 prompt tokens, in the traced window."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    _, _, toks = layers.prefill_work(ctx)
    s = layers.device_s(ctx.trace, layers.PREFILL)
    return s / toks * 1e6 if toks and s else None
