"""Share of the roofline reached by the prefill programs: the least time
of the traced prefill waves' work (``work.prefill_wave``: real tokens,
segment-aware causal attention) over their device time, in % (steady cells, where prefill waves stall the decode batch)."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    _, least, n = layers.prefill_work(ctx)
    s = layers.device_s(ctx.trace, layers.PREFILL)
    return least / s * 100.0 if n and s else None
