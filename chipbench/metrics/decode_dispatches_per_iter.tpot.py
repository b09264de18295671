"""Decode program dispatches per decode iteration over the window (engine
counters ``n_decode_dispatches`` and ``decode_iters``): 1 without megastep
fusion, 1/K with K iterations fused per dispatch."""


def read(ctx):
    it = ctx.counters.get("decode_iters", 0)
    if not it:
        return None
    return ctx.counters["decode_dispatches"] / it
