"""Engine spans on the profiler timeline, request lifecycle stamps and the
run-ahead counters (``repro.obs.spans``, ``serving/engine.py``).

  * spans change no served token and no sync count, and every ``engine.*``
    span a run reaches lands on the host plane of its ``.xplane.pb``;
  * with the switch off a profiled run records no engine span;
  * every request's stamps are ordered submit <= admitted <= scheduled <=
    first sampled <= done, and an arrival held behind an open megastep
    window is admitted later than it was submitted;
  * the run-ahead mean is positive on the async megastep path and no
    dispatch counts more than (max_pending + 1) * decode_megastep.
"""
import glob

import jax
import numpy as np
import pytest

from repro.obs import MetricsRegistry, publish_engine, spans
from repro.serving import GenRequest, SamplingParams, ServingEngine
from repro.serving.engine import EngineConfig

SPANS = {"engine.admit", "engine.form_batch", "engine.prefill",
         "engine.decode", "engine.drain_wait", "engine.finish"}


def _tiny_cfg():
    from repro.configs import get_config
    return get_config("qwen3_8b").reduced(layers=1).with_(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=128, dtype="float32", param_dtype="float32")


def _serve(ecfg=None, seed=5):
    """Three requests at t = 0, then one submitted while a megastep window
    is open; returns the engine, the requests (the held one last) and the
    run-ahead sum's growth per step."""
    cfg = _tiny_cfg()
    eng = ServingEngine(cfg, max_batch=4, capacity=128, rl_accuracy=1.0,
                        seed=seed, engine_cfg=ecfg)
    rng = np.random.default_rng(seed)

    def req(n_out):
        return GenRequest(prompt=list(rng.integers(1, cfg.vocab_size, 10)),
                          params=SamplingParams(max_new_tokens=n_out,
                                                temperature=0.0))

    reqs = [req(24) for _ in range(3)]
    t = 0.0
    for g in reqs:
        eng.submit(g, t)
    growth = []

    def step():
        nonlocal t
        t += 1.0
        before = eng.ahead_iters_sum
        eng.step(t)
        growth.append(eng.ahead_iters_sum - before)

    while eng._mega_left == 0:
        step()
    held = req(6)
    eng.submit(held, t + 0.5)
    assert held.t_admitted is None
    reqs.append(held)
    while eng.has_work():
        step()
    eng.flush()
    return eng, reqs, growth


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    return [e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_spans_change_no_token_and_land_on_the_host_plane(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(spans, "enabled", False)
    off, reqs_off, _ = _serve()
    monkeypatch.setattr(spans, "enabled", True)
    with jax.profiler.trace(str(tmp_path)):
        on, reqs_on, growth = _serve()
    assert [g.output for g in reqs_on] == [g.output for g in reqs_off]
    assert on.sync_counts == off.sync_counts
    names = _host_events(tmp_path)
    assert {n for n in names if n.startswith("engine.")} == SPANS
    # one form_batch per step; prefill, decode and finish per step with a
    # plan, so at most as many
    n_steps = len(growth)
    assert names.count("engine.form_batch") == n_steps
    for name in ("engine.prefill", "engine.decode", "engine.finish"):
        assert 0 < names.count(name) <= n_steps


def test_switched_off_spans_record_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "enabled", False)
    with jax.profiler.trace(str(tmp_path)):
        _serve()
    assert not [n for n in _host_events(tmp_path)
                if n.startswith("engine.")]


def test_lifecycle_stamps_are_ordered():
    _, reqs, _ = _serve()
    for g in reqs:
        assert g.status == "completed"
        assert (g.t_submit <= g.t_admitted <= g.t_scheduled
                <= g.t_first_sampled <= g.t_done), g


def test_held_arrival_is_admitted_after_its_window():
    _, reqs, _ = _serve()
    held = reqs[-1]
    assert held.t_admitted > held.t_submit
    assert all(g.t_admitted == g.t_submit for g in reqs[:-1])


@pytest.mark.parametrize("max_pending,megastep", [(8, 8), (1, 4)])
def test_run_ahead_is_positive_and_bounded(max_pending, megastep):
    ecfg = EngineConfig(max_pending=max_pending, decode_megastep=megastep)
    eng, _, growth = _serve(ecfg)
    assert eng.n_ahead_samples > 0
    assert eng.ahead_iters_sum / eng.n_ahead_samples > 0
    assert max(growth) <= (max_pending + 1) * megastep
    reg = MetricsRegistry()
    publish_engine(eng, reg, instance="0")
    snap = reg.snapshot()
    assert snap.get("engine_run_ahead_iters_total", instance="0") \
        == eng.ahead_iters_sum
    assert snap.get("engine_run_ahead_samples_total", instance="0") \
        == eng.n_ahead_samples
