"""The engine's spans in a trace (``chipbench/program_trace.py``) and the
metric readers built on them: innermost-span idle attribution over nested
harness and program spans; the recorded chip trace, which holds no program
span, reads as the harness alone; a CPU trace of a tiny engine gives every
reader a number."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from chipbench import program_trace as pt  # noqa: E402
from chipbench import run  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

SAMPLE = Path(__file__).parent / "data" / "sample_trace.xplane.pb"
READERS = ("drain_wait_p99_ms.tpot", "engine_sched_ms_per_iter.tpot",
           "run_ahead_iters.tpot")


def test_program_idle_goes_to_the_innermost_span():
    spans = [(0, 10, "step"), (1, 3, "engine.form_batch"),
             (1.5, 2.5, "form_batch"), (4, 9, "engine.decode"),
             (5, 8, "_drain_tokens"), (6, 7, "engine.drain_wait"),
             (12, 13, "submit")]
    busy = [[(0, 1.2), (2, 4.5), (5.5, 6.5), (8.5, 12.5)]]
    got = pt.idle_by_span(spans, busy, 0, 14)
    assert got == pytest.approx({
        "engine.form_batch": 0.3, "form_batch": 0.5,
        "engine.decode": 0.5 + 0.5, "_drain_tokens": 0.5 + 1.0,
        "engine.drain_wait": 0.5, "submit": 0.5, tr.OTHER: 1.0})
    assert sum(got.values()) == pytest.approx(14 - 1.2 - 2.5 - 1 - 4)


def test_recorded_chip_trace_has_no_program_spans(monkeypatch):
    names = ("step", "form_batch", "_drain_tokens")
    r = tr.reduce(str(SAMPLE), names)
    p = pt.reduce(str(SAMPLE), r.window, names)
    assert p.durations == {} and p.run_ahead is None
    assert p.idle_by_span == pytest.approx(r.idle_by_span, rel=1e-6)
    monkeypatch.setattr(run, "TRACE_DIR", str(SAMPLE.parent))
    ctx = SimpleNamespace(trace=r)
    assert [run.reader(m)(ctx) for m in READERS] == [None] * len(READERS)
    assert all(run.reader(m)(SimpleNamespace(trace=None)) is None
               for m in READERS)


def test_readers_on_an_engine_trace(tmp_path, monkeypatch):
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.serving import GenRequest, SamplingParams, ServingEngine

    cfg = get_config("qwen3_8b").reduced(layers=1).with_(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=128, dtype="float32", param_dtype="float32")
    eng = ServingEngine(cfg, max_batch=4, capacity=128, seed=1)
    rng = np.random.default_rng(1)
    reqs = [GenRequest(prompt=list(rng.integers(1, 128, 8)),
                       params=SamplingParams(max_new_tokens=20))
            for _ in range(4)]
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    # a CPU trace has no device plane: read the whole of it
    p = pt.reduce(str(tmp_path), (0.0, 1e6), run.SPANS)
    assert len(p.durations["engine.form_batch"]) >= eng.decode_iters
    assert 0 < p.run_ahead[1] <= eng.n_ahead_samples
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    ctx = SimpleNamespace(trace=SimpleNamespace(window=(0.0, 1e6)))
    got = {m: run.reader(m)(ctx) for m in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["run_ahead_iters.tpot"] > 0
