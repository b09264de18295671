"""Model FLOP/s of the decode iterations in the traced window over the
chip's bf16 peak, in %: FLOPs per output token times output tokens per
second."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    flops, _, n = layers.decode_work(ctx)
    if not n:
        return None
    return flops / ctx.window_s / ctx.peak["bf16_flops_per_s"] * 100.0
