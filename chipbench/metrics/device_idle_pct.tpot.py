"""Share of the traced window in which no operation ran on the device,
in % (steady cells)."""


def read(ctx):
    t = ctx.trace
    return None if t is None else t.idle_share * 100.0
