"""A whole run of the harness (the chip check skipped) at a tiny size on
the CPU: a sound run is ``correct``; the float8 control, and each fault a
serving cell can have planted in the timed path, are not."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax.numpy as jnp  # noqa: E402

from chipbench import run  # noqa: E402

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())
MIX = {"kind": "steady",
       "prompt": {"mean": 20, "sigma": 0.6, "min": 4, "max": 60},
       "output": {"mean": 12, "sigma": 0.7, "min": 2, "max": 40},
       "rl_corr": 0.45, "rate": 20.0, "lead_in_s": 0.3, "tail_s": 0.3,
       "drain_limit_s": 20.0, "check": {"served_tokens": 96}}
LIMIT = TINY["check"]["logit_gap_limit"]


def tiny_run(control=False, seed=2**33 + 17):
    cell = run.Cell("tiny.steady", 1, TINY, MIX,
                    ["tpot_p95_ms", "setup_s"], [])
    out = run.run_cell(cell, seed, 1.0, False, require_tpu=False,
                       control=control, log=lambda *a: None)
    assert out["attempted"] == 20 and out["failed"] == 0
    assert out["check"]["tokens_compared"]["value"] >= 96
    return out


def test_sound_run_is_correct_and_the_control_is_not():
    out = tiny_run(control=True)
    assert out["correct"], out["check"]
    assert out["check"]["logit_gap"]["value"] <= LIMIT
    assert out["check"]["control_gap"]["value"] > LIMIT
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert list(out)[-1] == "check"


def _state_unchanged(orig):
    def decode_step(cfg, p, tokens, pos, caches, impl="xla"):
        logits, _ = orig(cfg, p, tokens, pos, caches, impl=impl)
        return logits, caches
    return decode_step


def _half_batch_left_out(orig):
    def decode_step(cfg, p, tokens, pos, caches, impl="xla"):
        logits, new = orig(cfg, p, tokens, pos, caches, impl=impl)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]], 0), new
    return decode_step


def _token_altered(orig):
    def sample_in_graph(logits, *a, **kw):
        t = orig(logits, *a, **kw)
        return jnp.where(t % 3 == 0, (t + 1) % logits.shape[-1], t)
    return sample_in_graph


@pytest.mark.parametrize("target,fault", [
    ("repro.models.model.decode_step", _state_unchanged),
    ("repro.models.model.decode_step", _half_batch_left_out),
    ("repro.serving.engine.sample_in_graph", _token_altered),
], ids=["state_unchanged", "half_batch_left_out", "token_altered"])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, target,
                                                  fault):
    import importlib
    mod, name = target.rsplit(".", 1)
    m = importlib.import_module(mod)
    monkeypatch.setattr(m, name, fault(getattr(m, name)))
    out = tiny_run()
    assert not out["correct"]
    assert out["check"]["logit_gap"]["value"] > LIMIT
