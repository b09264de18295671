"""``chipbench/ttft_parts.py`` at a tiny size on the CPU: every request's
TTFT splits into lateness, hold, PT wait, prefill and first-token lag, each
of them non-negative, and the engine's run-ahead counters are read."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import run, traffic, ttft_parts, weights  # noqa: E402

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())
MIX = {"kind": "steady",
       "prompt": {"mean": 20, "sigma": 0.6, "min": 4, "max": 60},
       "output": {"mean": 12, "sigma": 0.7, "min": 2, "max": 40},
       "rl_corr": 0.45, "rate": 20.0, "lead_in_s": 0.3, "tail_s": 0.3,
       "drain_limit_s": 20.0}


def test_ttft_parts_add_up(monkeypatch):
    import jax
    from repro.launch.serve import build_server
    seed = 2**33 + 5
    cell = run.Cell("tiny.steady", 1, TINY, MIX, [], [])
    args, cfg = run.program_config(TINY)
    params = weights.make(TINY, traffic.derived_seed(seed, 1),
                          jax.devices()[0])
    engine = build_server(cfg, args, params)
    rows, summary = [], ttft_parts.summary
    monkeypatch.setattr(ttft_parts, "summary",
                        lambda r: rows.extend(r) or summary(r))
    line = ttft_parts.measure(engine, cell, seed, 1.0, run.Tracer(),
                              run.Compiles())
    assert line["window_requests"] == 20 and line["failed"] == 0
    assert line["spans"] is True and line["run_ahead_iters_drive"] > 0
    assert len(rows) == 20
    for r in rows:
        assert all(r[k] >= 0 for k in ttft_parts.PARTS), r
        assert sum(r[k] for k in ttft_parts.PARTS) == pytest.approx(
            r["ttft"], abs=1e-9)
    assert line["ttft_p50_ms"] > 0 and line["parts_p50_sum_ms"] > 0
    assert sum(line[k + "_mean_ms"] for k in ttft_parts.PARTS) == \
        pytest.approx(line["ttft_mean_ms"])
