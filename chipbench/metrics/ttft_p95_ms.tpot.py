"""95th percentile over the requests due in the window of the time from a
request's due time to its first visible token; a miss is +inf (printed as
1e9). Per-layer in the steady cells: host stalls make it swing far more
from run to run than a bound may allow (PERF.md)."""
from chipbench import stats


def read(ctx):
    v = ctx.stats.get("ttft_p95_s")
    return None if v is None else stats.finite(v * 1e3)
