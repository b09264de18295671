"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a model
configuration (``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``). Each metric the cell reports is
read by ``chipbench/metrics/<metric>.py``. Adding a configuration, a mix
or a metric is adding such a file and an entry; nothing here changes.

A run:

1. names the device and exits 2, printing no result, unless JAX finds a
   TPU with as many chips as the cell asks for;
2. makes the weights on the device from ``--seed`` (``weights.py``) and
   builds the server through ``repro.launch.serve`` (``model_config``,
   ``build_server``) at the configuration file's sizes;
3. warms every program the cell's traffic can reach: one request per
   packed-prefill bucket up to the largest wave the mix can form, the
   decode programs, and (mixes that chunk prompts) a rehearsal of the mix
   from a fixed seed, aborted once it has run;
4. drives ``ServingEngine.submit`` / ``step`` / ``flush`` on the wall
   clock through a lead-in and the measured window (``stats.py`` says
   what is counted), then follows the window's requests to completion;
5. compares a sample of the served tokens, drawn from the seed, with the
   plain float32 reference (``check.py``) and prints ``correct`` with
   each compared number beside its limit.

``setup_s`` runs from process start to the start of the lead-in. With
``--trace 1`` the first ``trace_s`` seconds of the window are traced and
the result carries the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")

sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import check, stats, traffic, weights, work  # noqa: E402

SPANS = ("submit", "step", "flush", "form_batch", "_run_prefill",
         "_drain_tokens")
DEFAULT_TRACE_S = 10.0


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[str]
    per_layer: List[str]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration,
    its traffic mix and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m["name"] for m in bench["end_to_end"] if reports(m)]
    per_layer = [m["name"] for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def reader(metric: str) -> Callable:
    """``read(ctx)`` of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Compiles:
    """Counts JAX traces and backend compiles, by phase. One listener is
    registered per process and feeds the newest counter."""
    current = None

    def __init__(self):
        if Compiles.current is None:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                lambda event, secs, **kw: Compiles.current._on(event, secs))
        Compiles.current = self
        self.phase = "setup"
        self.counts: Dict[str, Dict[str, float]] = {}

    def _on(self, event: str, secs: float) -> None:
        if event.endswith("backend_compile_duration"):
            kind = "compiles"
        elif event.endswith("jaxpr_trace_duration"):
            kind = "traces"
        else:
            return
        c = self.counts.setdefault(self.phase, {})
        c[kind] = c.get(kind, 0) + 1
        c[kind + "_s"] = c.get(kind + "_s", 0.0) + secs

    def of(self, phase: str, kind: str) -> float:
        return self.counts.get(phase, {}).get(kind, 0)


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says; the program takes it from
    ``JAX_COMPILATION_CACHE_DIR``. Every program is cached, however fast
    it compiled, so that a warm run compiles nothing. Call before the
    first compile of the process."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# --------------------------------------------------------------------- #
# building and warming
# --------------------------------------------------------------------- #
def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: built by
    ``repro.launch.serve.model_config`` at the file's depth, then held to
    every size the file states."""
    from repro.launch.serve import model_config, parse_args
    s = config["serving"]
    args = parse_args(["--arch", config["arch"], "--layers",
                       str(config["num_hidden_layers"]), "--max-batch",
                       str(s["max_batch"]), "--capacity", str(s["capacity"]),
                       "--impl", s["impl"], "--seed", "0"])
    cfg = model_config(args).with_(
        num_layers=config["num_hidden_layers"], layer_pattern=None,
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rms_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        use_qk_norm=bool(config.get("qk_norm")),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        sliding_window=None, num_experts=0,
        dtype=config["torch_dtype"], param_dtype=config["torch_dtype"])
    return args, cfg


def serve_until_idle(engine, reqs, limit_s: float = 600.0) -> None:
    t0 = time.perf_counter()
    for g in reqs:
        engine.submit(g, time.perf_counter() - t0)
    while engine.has_work():
        if time.perf_counter() - t0 > limit_s:
            raise RuntimeError("warm-up did not finish")
        engine.step(time.perf_counter() - t0)
    engine.flush()


def warm(engine, mix: dict, items, vocab: int) -> Dict[str, float]:
    """Compile (or load from the cache) every program the mix can reach."""
    from repro.serving import GenRequest, SamplingParams
    from repro.serving.engine import seq_bucket
    cap = engine.capacity
    lens = [len(it.prompt) for it in items]
    wave = min(cap, engine.max_batch * max(lens))
    buckets = sorted({seq_bucket(n) for n in range(min(lens), wave + 1)})
    t = {}
    t0 = time.perf_counter()
    for i, b in enumerate(buckets):
        n = min(b, cap - 3)
        serve_until_idle(engine, [GenRequest(
            prompt=[1 + (7 * j) % (vocab - 1) for j in range(n)],
            params=SamplingParams(max_new_tokens=12 if i == 0 else 2))])
    t["buckets"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if mix.get("rehearse_s"):
        rehearse(engine, mix, vocab)
    t["rehearsal"] = time.perf_counter() - t0
    return t


def rehearse(engine, mix: dict, vocab: int) -> None:
    """Run the mix itself, from a fixed seed, for ``rehearse_s`` seconds,
    then abort what is left and let the engine go idle: it reaches the
    chunked-prefill shapes that only a crowd of prompts forms."""
    from repro.serving import GenRequest, SamplingParams
    sched = traffic.schedule(mix, 0, float(mix["rehearse_s"]), vocab)
    gens = [GenRequest(prompt=it.prompt,
                       params=SamplingParams(max_new_tokens=it.out_len))
            for it in sched]
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(sched) and sched[i].due <= now:
            engine.submit(gens[i], now)
            i += 1
        if now >= float(mix["rehearse_s"]):
            break
        if engine.has_work():
            engine.step(now)
        else:
            time.sleep(0.001)
    for g in gens[:i]:
        if not g.finished:
            engine.abort(g.rid, time.perf_counter() - t0, "warm-up over")
    while engine.has_work():
        engine.step(time.perf_counter() - t0)
    engine.flush()


# --------------------------------------------------------------------- #
# the measured run
# --------------------------------------------------------------------- #
@dataclass
class Tracer:
    """Host spans and plan records of the traced part of the window:
    ``enabled`` in a traced run, ``on`` while the profiler runs."""
    enabled: bool = False
    on: bool = False
    plans: List[tuple] = field(default_factory=list)

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def instrument(engine, tracer: Tracer) -> None:
    """Wrap, on this engine instance, the scheduler's ``form_batch`` (span
    and plan record) and, where the engine has them, ``_run_prefill`` and
    ``_drain_tokens`` (spans). Active only while the tracer is on; a
    method that is gone is left alone, and its idle time falls to the
    enclosing span."""
    sched = engine.scheduler
    form = sched.form_batch

    def form_batch(t):
        if not tracer.on:
            return form(t)
        with tracer.span("form_batch"):
            plan = form(t)
        tracer.plans.append((
            [r.prompt_len + r.generated for r in plan.decode_reqs],
            [(c, r.prompt_done, r.prompt_done + c >= r.prompt_len)
             for r, c in plan.prompt_items]))
        return plan

    sched.form_batch = form_batch
    for name in ("_run_prefill", "_drain_tokens"):
        fn = getattr(engine, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with tracer.span(_name):
                return _fn(*a, **kw)

        setattr(engine, name, wrapped)


def counters(engine) -> Dict[str, float]:
    return {"decode_dispatches": engine.n_decode_dispatches,
            "decode_iters": engine.decode_iters,
            "prefill_waves": engine.n_prefill_waves,
            "chunk_calls": engine.n_chunk_calls,
            "tokens_drained": engine.n_tokens_drained,
            "prefill_compiles": engine.n_prefill_compiles,
            "blocking_syncs": engine.n_blocking_syncs}


def drive(engine, items, mix: dict, seconds: float, tracer: Tracer,
          trace_s: float, comp: Compiles, log=say):
    """Serve ``items`` on the wall clock. Returns the client records, the
    requests as served, the window bounds and the engine counters over
    the window."""
    import jax
    from repro.serving import GenRequest, SamplingParams
    gens = [GenRequest(prompt=it.prompt,
                       params=SamplingParams(max_new_tokens=it.out_len))
            for it in items]
    recs = [stats.Record(it.due, it.segment, len(it.prompt), it.out_len)
            for it in items]
    batch = mix["kind"] == "batch"
    w0 = float(mix["lead_in_s"])
    w1 = w0 + seconds
    drain_s = float(mix.get("drain_limit_s", 0.0))
    live = set()
    late: List[float] = []
    state = {"c0": None, "c1": None, "trace_end": None, "ann": None}
    pending_window = [i for i, r in enumerate(recs) if r.segment == "window"]

    def stamp(now):
        for i in list(live):
            g, r = gens[i], recs[i]
            k = len(g.output)
            if k > len(r.stamps):
                r.stamps.extend([now] * (k - len(r.stamps)))
            if g.finished:
                r.status = g.status or "completed"
                r.t_end = now
                live.discard(i)

    def open_window(now):
        state["c0"] = counters(engine)
        comp.phase = "window"
        if tracer.enabled:
            state["ann"] = jax.profiler.TraceAnnotation("window")
            state["ann"].__enter__()
            tracer.on = True
            state["trace_end"] = now + trace_s

    def close_trace():
        state["ann"].__exit__(None, None, None)
        tracer.on = False
        state["trace_end"] = None

    if tracer.enabled:
        jax.profiler.start_trace(TRACE_DIR)
    t0 = time.perf_counter()
    i, n = 0, len(items)
    opened = closed = False
    while True:
        now = time.perf_counter() - t0
        if not opened and now >= w0:
            open_window(now)
            opened = True
        if state["trace_end"] is not None and now >= state["trace_end"]:
            close_trace()
        if opened and not closed and now >= w1:
            state["c1"] = counters(engine)
            comp.phase = "drain"
            closed = True
            if batch:
                break
        if closed and (now >= w1 + drain_s
                       or all(recs[j].status for j in pending_window)):
            break
        while i < n and items[i].due <= now:
            with tracer.span("submit"):
                engine.submit(gens[i], now)
            late.append(now - items[i].due)
            live.add(i)
            i += 1
        if engine.has_work():
            with tracer.span("step"):
                engine.step(now)
            stamp(time.perf_counter() - t0)
        elif batch:
            raise RuntimeError(f"the backlog ran dry at {now:.3f} s, before "
                               f"the window closed at {w1:.3f} s")
        else:
            edge = w0 if not opened else (w1 if not closed else w1 + drain_s)
            wait = min(items[i].due if i < n else edge, edge) - now
            if wait > 0:
                time.sleep(min(wait, 0.002))
    if state["trace_end"] is not None:
        close_trace()
    engine.flush()
    stamp(time.perf_counter() - t0)
    if tracer.enabled:
        jax.profiler.stop_trace()
    comp.phase = "after"
    c0, c1 = state["c0"], state["c1"]
    delta = {k: c1[k] - c0[k] for k in c0}
    late.sort()
    log(f"generator: {len(late)} submitted, late p50 "
        f"{stats.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{stats.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late[-1] * 1e3:.3f} ms" if late else "generator: none submitted")
    return recs, gens, (w0, w1), delta


# --------------------------------------------------------------------- #
@dataclass
class Ctx:
    """What a metric reader gets."""
    cell: Cell
    dims: work.Dims
    peak: dict
    window_s: float
    stats: dict
    setup_s: float
    counters: Dict[str, float]
    trace: object = None          # trace_reduce.Reduced of the traced run
    plans: list = field(default_factory=list)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: bool = False,
             log=say) -> Optional[dict]:
    """One run of ``cell``. Returns the result object, or None when the
    device is not the one the cell needs. ``control`` also reads the
    float8 control on the same sample (``calibrate.py``; never in the
    benchmark's own runs)."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        log(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devs)} {dev.platform} device(s) "
            f"({dev.device_kind})")
        return None
    comp = Compiles()
    from repro.launch.serve import build_server
    config, mix = cell.config, cell.traffic
    peak = work.peaks(dev.device_kind) if require_tpu else None
    args, cfg = program_config(config)
    parts = {}
    t0 = time.perf_counter()
    params = weights.make(config, traffic.derived_seed(seed, 1), dev)
    jax.block_until_ready(params)
    parts["weights"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = build_server(cfg, args, params)
    parts["build"] = time.perf_counter() - t0
    trace_s = float(mix.get("trace_s", DEFAULT_TRACE_S))
    window = min(seconds, trace_s) if trace else seconds
    items = traffic.schedule(mix, seed, window, config["vocab_size"])
    for k, v in warm(engine, mix, items, config["vocab_size"]).items():
        parts["warm_" + k] = v
    tracer = Tracer(enabled=trace)
    instrument(engine, tracer)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    setup_s = time.perf_counter() - T_START
    comp.phase = "lead_in"
    recs, gens, (w0, w1), delta = drive(engine, items, mix, window, tracer,
                                        trace_s, comp, log)
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in parts.items())
        + f", total {setup_s:.3f} s; compiles in setup "
        f"{comp.of('setup', 'compiles'):.0f} "
        f"({comp.of('setup', 'compiles_s'):.3f} s); lead-in {w0:.1f} s")
    delta["compiles_in_lead_in"] = comp.of("lead_in", "compiles")
    delta["compiles_in_window"] = comp.of("window", "compiles")
    delta["traces_in_window"] = comp.of("window", "traces")
    log("window counters: " + json.dumps(delta))
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if mix["kind"] == "batch":
        st = stats.batch(recs, w0, w1)
    else:
        st = stats.steady(recs)
    reduced = None
    if trace:
        from chipbench import trace_reduce
        reduced = trace_reduce.reduce(TRACE_DIR, SPANS)
    served = [(g.prompt, list(g.output), r) for g, r in zip(gens, recs)
              if r.done]
    del engine
    gc.collect()
    verdict = check.verify(params, config, mix, seed, served, log,
                          control=control)
    del params
    ctx = Ctx(cell, work.Dims.of(config), peak, w1 - w0, st,
              setup_s, delta, reduced, tracer.plans)
    names = cell.per_layer if trace else cell.end_to_end
    units = metric_units()
    metrics = {}
    for m in names:
        v = reader(m)(ctx)
        if v is not None:
            metrics[m] = {"value": v, "unit": units.get(m, "")}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(mem)}
    out = {"correct": verdict["correct"], "attempted": st["attempted"],
           "failed": st["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        from chipbench import trace_reduce
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {
            "device_ops": trace_reduce.top(reduced.op_s),
            "idle_gaps": trace_reduce.top(reduced.idle_by_span)}
    out["check"] = verdict["numbers"]
    for k, v in verdict["numbers"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return out


def metric_units() -> Dict[str, str]:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        bench = json.load(f)
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = load_cell(a.workload)
    use_checkout_cache()
    out = run_cell(cell, a.seed, a.seconds, bool(a.trace))
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
