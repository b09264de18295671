"""The stratified traffic generator offers every seed the same work."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench import traffic  # noqa: E402

STEADY = {"kind": "steady",
          "prompt": {"mean": 19.31, "sigma": 0.6, "min": 9, "max": 1756},
          "output": {"mean": 58.41, "sigma": 0.7, "min": 13, "max": 292},
          "rl_corr": 0.45, "rate": 6.0, "lead_in_s": 5.0, "tail_s": 10.0}
BATCH = {"kind": "batch",
         "prompt": {"mean": 161.31, "sigma": 0.9, "min": 16, "max": 1024},
         "output": {"mean": 337.99, "sigma": 0.7, "min": 19, "max": 991},
         "rl_corr": 0.45, "backlog": 200, "lead_in_s": 4.0}
SEEDS = [0, 1, 2**31 + 11, 3_000_000_007]


def work(items, segment):
    return Counter((len(it.prompt), it.out_len)
                   for it in items if it.segment == segment)


@pytest.mark.parametrize("spec", [STEADY, BATCH], ids=["steady", "batch"])
def test_every_seed_offers_the_same_multiset(spec):
    runs = [traffic.schedule(spec, s, 30.0, 1000) for s in SEEDS]
    assert all(work(r, "window") == work(runs[0], "window") for r in runs)
    orders = {tuple(len(it.prompt) for it in r) for r in runs}
    assert len(orders) == len(runs), "the seed must permute the order"


def test_gaps_are_the_same_multiset_and_fill_each_segment():
    lead, secs = STEADY["lead_in_s"], 30.0
    base = None
    for s in SEEDS:
        items = traffic.schedule(STEADY, s, secs, 1000)
        due = np.array([it.due for it in items if it.segment == "window"])
        assert len(due) == round(STEADY["rate"] * secs)
        assert lead < due.min() and abs(due.max() - (lead + secs)) < 1e-9
        g = np.sort(np.diff(np.concatenate([[lead], due])))
        if base is None:
            base = g
        np.testing.assert_allclose(g, base, rtol=1e-9, atol=1e-9)


def test_lengths_follow_the_mix():
    p, o = traffic.pairs(2000, traffic.Lengths.of(BATCH["prompt"]),
                         traffic.Lengths.of(BATCH["output"]), 0.45)
    assert p.min() >= 16 and p.max() <= 1024
    assert o.min() >= 19 and o.max() <= 991
    assert abs(p.mean() / 161.31 - 1) < 0.05
    assert abs(o.mean() / 337.99 - 1) < 0.05
    r = np.corrcoef(np.log(p), np.log(o))[0, 1]
    assert 0.3 < r < 0.6


def test_large_seeds_stay_distinct():
    a = traffic.schedule(BATCH, 5, 1.0, 1000)
    b = traffic.schedule(BATCH, 2**33 + 5, 1.0, 1000)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert traffic.derived_seed(5, 1) != traffic.derived_seed(2**33 + 5, 1)
    assert 0 <= traffic.derived_seed(2**40, 1) < 2**31
