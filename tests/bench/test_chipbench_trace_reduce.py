"""Trace reduction: interval arithmetic, and a trace recorded on a TPU v5e
by ``chipbench/record_sample_trace.py``."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from chipbench import trace_reduce as tr  # noqa: E402

SAMPLE = Path(__file__).parent / "data" / "sample_trace.xplane.pb"


def test_union_and_complement():
    u = tr.union([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert u == [(0, 4), (5, 6)]
    assert tr.complement(u, -1, 8) == [(-1, 0), (4, 5), (6, 8)]
    assert tr.clip([(0, 4), (5, 6)], 1, 5.5) == [(1, 4), (5, 5.5)]


def test_idle_goes_to_the_innermost_open_span():
    spans = tr.Spans([(0, 10, "step"), (1, 3, "form_batch"),
                      (5, 6, "_drain_tokens"), (12, 13, "submit")])
    assert spans.innermost(2) == "form_batch"
    assert spans.innermost(4) == "step"
    assert spans.innermost(11) == tr.OTHER
    got = spans.attribute([(2, 5.5), (11, 12.5)])
    assert got == pytest.approx({"form_batch": 1.0, "step": 2.0,
                                 "_drain_tokens": 0.5, tr.OTHER: 1.0,
                                 "submit": 0.5})


def test_recorded_chip_trace():
    assert SAMPLE.exists(), f"missing {SAMPLE}"
    r = tr.reduce(str(SAMPLE), ("step", "form_batch", "_drain_tokens"))
    assert r.n_devices == 1
    # no "window" span in this trace: the window is the extent of the
    # device ops, from the first of the three matmul-chain programs to
    # the last, so two of the three 20 ms sleeps in form_batch and two of
    # the 10 ms sleeps between steps fall inside it
    assert r.window_s == pytest.approx(0.0652, abs=0.001)
    assert r.module_calls == {"jit_chain(1380526381424456196)": 3}
    assert r.busy_s == pytest.approx(sum(r.module_s.values()), rel=0.05)
    assert 0.038 < r.idle_by_span["form_batch"] < 0.045
    assert 0.018 < r.idle_by_span[tr.OTHER] < 0.026
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert r.span_n == {"step": 2, "form_batch": 2, "_drain_tokens": 2}
    assert "convolution_tanh_fusion.3" in r.op_s


def test_nested_ops_count_their_own_time():
    evs = [("while.1", 0, 10), ("fusion.1", 1, 4), ("fusion.2", 5, 9),
           ("copy.1", 12, 13)]
    got = dict(tr.self_times(evs))
    assert got == {"while.1": 3, "fusion.1": 3, "fusion.2": 4, "copy.1": 1}
