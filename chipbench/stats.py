"""Window arithmetic: which requests count, and the statistics over them.

A steady run counts the requests *due* inside the measured window and
follows each to completion, up to a drain limit. One that failed, was
aborted or shed, or is unfinished at the limit is a miss: +inf in every
latency tail, and ``failed``. Latencies run from the request's due time to
the moment a token became visible to the client (after a drain).

A batch run counts the tokens that became visible inside the window; a
request still in flight when the window closes is cut, and counts neither
as attempted nor as failed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

INF = float("inf")


@dataclass
class Record:
    """What the client saw of one request."""
    due: float
    segment: str
    n_prompt: int
    n_out: int                       # tokens asked for
    stamps: List[float] = field(default_factory=list)   # visibility times
    status: Optional[str] = None     # terminal status, None while in flight
    t_end: Optional[float] = None    # when the terminal status was seen

    @property
    def done(self) -> bool:
        return self.status == "completed" and len(self.stamps) >= self.n_out


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. +inf entries (misses) sort last."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def ttft(r: Record) -> float:
    return r.stamps[0] - r.due if (r.done and r.stamps) else INF


def tpot(r: Record) -> float:
    if not r.done:
        return INF
    return (r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)


def steady(records: Sequence[Record]) -> dict:
    """Statistics over the requests due in the window."""
    counted = [r for r in records if r.segment == "window"]
    failed = sum(not r.done for r in counted)
    tt = [ttft(r) for r in counted]
    tp = [tpot(r) for r in counted if r.n_out >= 2]
    return {"attempted": len(counted), "failed": failed,
            "ttft_s": tt, "tpot_s": tp,
            "ttft_p95_s": percentile(tt, 95) if tt else None,
            "tpot_p95_s": percentile(tp, 95) if tp else None}


def batch(records: Sequence[Record], w0: float, w1: float) -> dict:
    """Statistics over the batch window [w0, w1)."""
    toks = sum(sum(w0 <= t < w1 for t in r.stamps) for r in records)
    ended = [r for r in records
             if r.t_end is not None and w0 <= r.t_end < w1]
    return {"attempted": len(ended),
            "failed": sum(r.status != "completed" for r in ended),
            "tokens": toks, "tokens_per_s": toks / (w1 - w0)}


def finite(x: float, cap: float = 1e9) -> float:
    """JSON has no infinity: a tail made of misses prints as ``cap``."""
    return cap if x == INF else x
