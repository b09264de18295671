"""Output tokens that became visible inside the window, over the window's
seconds (batch cells)."""


def read(ctx):
    return ctx.stats.get("tokens_per_s")
