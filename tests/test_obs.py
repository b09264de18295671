"""Metrics plane: registry semantics, exporters, sampler zero-overhead
contract, and the deterministic drain classification it relies on.

Covers the PR-9 acceptance checklist:
  * label-set identity (same values, any kwarg order -> same child);
  * histogram bucket edges: boundary values land low-side, the +Inf
    bucket conserves the total count;
  * counter monotonicity under concurrent publishers (threads);
  * snapshot immutability (frozen at capture, unaffected by later
    publishes);
  * Prometheus text round-trip and JSON exports;
  * enqueue-time drain classification is deterministic across repeated
    runs of the same stream (the PR-8 race this PR fixes);
  * a sampler-attached engine produces bitwise-identical tokens and
    identical sync totals to a bare one.
"""
import json
import math
import threading

import pytest

from repro.obs import (DEFAULT_BUCKETS, MetricsRegistry, MetricsSampler,
                       Snapshot, TimeSeriesLog, parse_prometheus_text,
                       publish_engine, to_prometheus_text,
                       write_json_snapshot)


# --------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------- #
def test_label_set_identity():
    reg = MetricsRegistry()
    fam = reg.counter("rpc_calls_total", "calls", ("method", "code"))
    a = fam.labels(method="get", code="200")
    b = fam.labels(code="200", method="get")     # kwarg order irrelevant
    assert a is b
    a.inc(3)
    assert b.value == 3.0
    c = fam.labels(method="get", code="500")
    assert c is not a and c.value == 0.0
    # label values are stringified consistently
    g = reg.gauge("inst_state", "", ("instance",))
    assert g.labels(instance=7) is g.labels(instance="7")


def test_label_validation_and_redeclare():
    reg = MetricsRegistry()
    fam = reg.counter("x_total", "", ("a",))
    with pytest.raises(ValueError):
        fam.labels(b="1")                        # undeclared label
    with pytest.raises(ValueError):
        fam.labels()                             # missing label
    # same signature: same family object; changed signature: refused
    assert reg.counter("x_total", "", ("a",)) is fam
    with pytest.raises(ValueError):
        reg.counter("x_total", "", ("a", "b"))
    with pytest.raises(ValueError):
        reg.gauge("x_total", "", ("a",))
    with pytest.raises(AssertionError):
        reg.counter("0bad", "")                  # invalid metric name


def test_counter_monotone_api():
    reg = MetricsRegistry()
    c = reg.counter("n_total", "").unlabeled
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    c.inc_to(10)
    with pytest.raises(ValueError):
        c.inc_to(9)                              # regression refused
    assert c.value == 10.0


def test_counter_monotonic_under_concurrent_publishers():
    reg = MetricsRegistry()
    child = reg.counter("hits_total", "", ("worker",)).labels(worker="w")
    n_threads, n_incs = 8, 2_000
    seen = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            seen.append(child.value)

    def writer():
        for _ in range(n_incs):
            child.inc(1)

    threads = [threading.Thread(target=writer) for _ in range(n_threads)]
    rt = threading.Thread(target=reader)
    rt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join()
    # no lost updates, and every observed value non-decreasing
    assert child.value == n_threads * n_incs
    assert all(a <= b for a, b in zip(seen, seen[1:]))


def test_histogram_boundary_low_side_and_inf_conserved():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "", buckets=(1.0, 2.0, 5.0)).unlabeled
    h.observe(1.0)            # exactly on an edge -> low-side bucket
    h.observe(1.0000001)      # just above -> next bucket
    h.observe(5.0)            # top finite edge
    h.observe(99.0)           # overflow -> +Inf only
    v = reg.snapshot().get("lat")
    assert v.buckets == ((1.0, 1), (2.0, 2), (5.0, 3),
                         (float("inf"), 4))
    assert v.count == 4 and v.buckets[-1][1] == v.count   # +Inf conserved
    assert v.sum == pytest.approx(1.0 + 1.0000001 + 5.0 + 99.0)
    # unsorted / +Inf-containing declarations are refused or normalized
    h2 = reg.histogram("lat2", "", buckets=(5.0, 1.0, 2.0)).unlabeled
    h2.observe(1.5)
    assert reg.snapshot().get("lat2").buckets[1] == (2.0, 1)
    with pytest.raises(AssertionError):
        reg.histogram("lat3", "", buckets=(1.0, float("inf")))


def test_snapshot_immutable_and_stable():
    reg = MetricsRegistry()
    c = reg.counter("a_total", "", ("k",)).labels(k="1")
    h = reg.histogram("h", "", buckets=(1.0,)).unlabeled
    c.inc(5)
    h.observe(0.5)
    snap = reg.snapshot()
    # later publishes don't leak into the captured snapshot
    c.inc(100)
    h.observe(0.2)
    assert snap.get("a_total", k="1") == 5.0
    assert snap.get("h").count == 1
    # the snapshot's structures refuse mutation
    with pytest.raises(TypeError):
        snap.families[0].samples[0][0]["k"] = "2"
    with pytest.raises((TypeError, AttributeError)):
        snap.families[0].samples = ()
    with pytest.raises((TypeError, AttributeError)):
        snap.get("h").count = 7
    assert isinstance(snap, Snapshot)


def test_snapshot_flat_rendering():
    reg = MetricsRegistry()
    reg.counter("c_total", "", ("x",)).labels(x="a").inc(2)
    reg.gauge("g", "").unlabeled.set(1.5)
    reg.histogram("h", "", buckets=(1.0,)).unlabeled.observe(3.0)
    flat = reg.snapshot().flat()
    assert flat['c_total{x="a"}'] == 2.0
    assert flat["g"] == 1.5
    assert flat['h_bucket{le="1"}'] == 0
    assert flat['h_bucket{le="+Inf"}'] == 1
    assert flat["h_sum"] == 3.0 and flat["h_count"] == 1
    with pytest.raises(KeyError):
        reg.snapshot().get("nope")


# --------------------------------------------------------------------- #
# exporters
# --------------------------------------------------------------------- #
def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", ("code",)).labels(code="200") \
        .inc(7)
    reg.gauge("depth", "queue depth").unlabeled.set(3)
    reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)) \
        .unlabeled.observe(0.05)
    snap = reg.snapshot()
    text = to_prometheus_text(snap)
    parsed = parse_prometheus_text(text)
    assert parsed['req_total{code="200"}'] == 7.0
    assert parsed["depth"] == 3.0
    assert parsed['lat_seconds_bucket{le="+Inf"}'] == 1.0
    assert parsed["lat_seconds_count"] == 1.0
    # the parser rejects garbage rather than returning partial data
    with pytest.raises(ValueError):
        parse_prometheus_text("this is not prometheus\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("")


def test_json_snapshot_and_timeseries(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("v", "").unlabeled.set(2)
    p = tmp_path / "m.json"
    write_json_snapshot(reg.snapshot(), str(p), extra={"run": "t"})
    data = json.loads(p.read_text())
    assert data["metrics"]["v"] == 2.0 and data["meta"]["run"] == "t"

    log = TimeSeriesLog()
    log.record(0.0, {"a": 1.0})
    log.record(1.0, {"a": 2.0, "b": 5.0})
    log.record_snapshot(2.0, reg.snapshot())
    out = log.to_json()["series"]
    assert out["a"] == {"t": [0.0, 1.0], "v": [1.0, 2.0]}
    assert out["b"] == {"t": [1.0], "v": [5.0]}
    assert out["v"] == {"t": [2.0], "v": [2.0]}
    q = tmp_path / "ts.json"
    log.write(str(q))
    assert json.loads(q.read_text())["series"]["a"]["v"] == [1.0, 2.0]


# --------------------------------------------------------------------- #
# engine integration: sampler + deterministic drain classification
# --------------------------------------------------------------------- #
def _tiny_cfg():
    from repro.configs import get_config
    return get_config("qwen3_8b").reduced(layers=1).with_(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=128, dtype="float32", param_dtype="float32")


def _run_stream(cfg, sampler_reg=None, seed=3, n=5):
    import numpy as np
    from repro.serving import GenRequest, SamplingParams, ServingEngine

    eng = ServingEngine(cfg, max_batch=4, capacity=128, rl_accuracy=1.0,
                        seed=seed)
    if sampler_reg is not None:
        MetricsSampler(sampler_reg, instance="0").attach(eng)
    rng = np.random.default_rng(seed)
    reqs = [GenRequest(prompt=list(rng.integers(0, cfg.vocab_size, 12)),
                       params=SamplingParams(
                           max_new_tokens=int(rng.integers(4, 10)),
                           temperature=0.0))
            for _ in range(n)]
    eng.run(reqs, arrivals=[0.5 * i for i in range(n)])
    return eng, [tuple(g.output) for g in reqs]


def test_drain_classification_deterministic():
    """The PR-8 race: drain_blocking/backpressure used to be classified
    at pop time from ``toks.is_ready()`` — device timing. Classification
    now happens at enqueue from dispatch sequence numbers, so repeated
    runs of the same stream agree on every single count."""
    cfg = _tiny_cfg()
    counts = []
    for _ in range(3):
        eng, _ = _run_stream(cfg)
        counts.append(dict(eng.sync_counts))
    assert counts[0] == counts[1] == counts[2]
    # async engine: the only drain_blocking source is the sync fallback
    assert counts[0]["drain_blocking"] == 0


def test_sampler_bitwise_identity_and_zero_added_syncs():
    cfg = _tiny_cfg()
    bare, toks_off = _run_stream(cfg)
    reg = MetricsRegistry()
    sampled, toks_on = _run_stream(cfg, sampler_reg=reg)
    assert toks_on == toks_off
    assert sampled.sync_counts == bare.sync_counts
    snap = reg.snapshot()
    # the registry's totals mirror the engine's own counters
    for kind, v in sampled.sync_counts.items():
        assert snap.get("engine_host_syncs_total",
                        instance="0", kind=kind) == v
    assert snap.get("engine_decode_iters_total", instance="0") \
        == sampled.decode_iters
    assert snap.get("engine_tokens_drained_total", instance="0") \
        == sampled.n_tokens_drained > 0


def test_publish_engine_and_debug_state_agree():
    cfg = _tiny_cfg()
    eng, _ = _run_stream(cfg)
    reg = MetricsRegistry()
    publish_engine(eng, reg, instance="0")
    flat = reg.snapshot().flat()
    dbg = eng.debug_state()
    assert dbg == flat                 # one publication path, one answer
    assert 'scheduler_completed_total{instance="0"}' in dbg
    assert 'kvc_free_blocks{instance="0"}' in dbg


def test_sampler_handles_spawned_instances():
    """Fleet attach must also cover autoscaler-spawned engines (the
    registry reference is kept, not the sampler list)."""
    from repro.cluster import EngineFleet

    cfg = _tiny_cfg()
    fleet = EngineFleet(cfg, n_instances=2, router="least-kvc", seed=0,
                        max_batch=4, capacity=128, rl_accuracy=1.0)
    reg = MetricsRegistry()
    fleet.attach_metrics(reg)
    fleet._spawn(0.0)
    assert fleet.instances[-1].engine.metrics is not None
    samples = reg.snapshot().flat()
    assert 'sampler_samples_total{instance="2"}' in samples
