"""95th percentile over the requests due in the window, with two or more
output tokens, of (last - first visible token time) / (tokens - 1); a miss
is +inf (printed as 1e9)."""
from chipbench import stats


def read(ctx):
    v = ctx.stats.get("tpot_p95_s")
    return None if v is None else stats.finite(v * 1e3)
