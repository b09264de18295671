"""Window arithmetic: what counts, and misses as misses."""
import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench import stats  # noqa: E402

Rec = stats.Record


def done(due, stamps, segment="window"):
    return Rec(due, segment, 10, len(stamps), list(stamps), "completed",
               stamps[-1])


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_steady_counts_only_requests_due_in_the_window():
    recs = [done(0.0, [100.0, 101.0], "lead_in"),
            done(10.0, [10.5, 10.6]),
            done(20.0, [20.2, 20.4, 20.6]),
            done(99.0, [99.1, 99.2], "tail")]
    st = stats.steady(recs)
    assert st["attempted"] == 2 and st["failed"] == 0
    assert st["ttft_p95_s"] == pytest.approx(0.5)
    assert st["tpot_p95_s"] == pytest.approx(0.2)


def test_a_miss_is_infinite_in_every_tail():
    recs = [done(float(i), [i + 0.1, i + 0.2]) for i in range(19)]
    recs.append(Rec(30.0, "window", 10, 5, [30.5], None, None))
    st = stats.steady(recs)
    assert st["failed"] == 1 and st["attempted"] == 20
    assert st["ttft_p95_s"] == pytest.approx(0.1)     # 19 of 20 finite
    recs.append(Rec(31.0, "window", 10, 5, [], "aborted", 40.0))
    st = stats.steady(recs)
    assert st["failed"] == 2 and math.isinf(st["ttft_p95_s"])
    assert math.isinf(st["tpot_p95_s"])
    assert stats.finite(st["ttft_p95_s"]) == 1e9


def test_tpot_skips_single_token_requests():
    recs = [done(0.0, [0.5]), done(0.0, [0.5, 0.6])]
    assert stats.steady(recs)["tpot_s"] == [pytest.approx(0.1)]


def test_batch_counts_tokens_visible_inside_the_window_only():
    recs = [done(0.0, [0.5, 1.5, 2.5, 3.5]),            # ends after w1
            Rec(0.0, "window", 10, 9, [1.2, 1.8, 2.9], None, None),
            done(0.0, [2.0, 2.1])]                      # ends inside
    st = stats.batch(recs, 1.0, 3.0)
    assert st["tokens"] == 2 + 3 + 2
    assert st["tokens_per_s"] == pytest.approx(7 / 2.0)
    assert st["attempted"] == 1 and st["failed"] == 0   # in flight: cut
