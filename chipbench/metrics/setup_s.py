"""Seconds from process start to the start of the lead-in: weights,
server build, compile-cache loads or compiles, and warm-up."""


def read(ctx):
    return ctx.setup_s
