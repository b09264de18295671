"""Model FLOP/s of the prompt and output tokens served in the traced
window (prefill waves and decode iterations) over the chip's bf16 peak,
in %."""
from chipbench import layers


def read(ctx):
    if ctx.trace is None:
        return None
    fd, _, nd = layers.decode_work(ctx)
    fp, _, npf = layers.prefill_work(ctx)
    if not (nd or npf):
        return None
    return (fd + fp) / ctx.window_s / ctx.peak["bf16_flops_per_s"] * 100.0
