"""Put a steady cell's TTFT down to its parts, from the engine's request
stamps and spans.

    python3 -m chipbench.ttft_parts --workload <cell> --seeds <n>[,<n>...] \
        [--spans 1,0] [--trace 1] [--seconds <s>]

One process builds the cell's server once, warms it as ``chipbench.run``
does, then drives the cell's traffic once per seed and per setting of the
engine's span switch (``repro.obs.spans.enabled``; the order alternates
from seed to seed). With ``--trace 1`` each drive is traced as a benchmark
run is (``--seconds`` defaults to the mix's ``trace_s``). One line of JSON
per drive on standard output:

- over the requests due in the window, the median, p95 and mean of TTFT
  (due to first visible token) and of its parts, which add up to it
  request by request: ``late`` (due to ``t_submit``: the generator's
  lateness), ``hold`` (to ``t_admitted``: an open megastep window held
  the arrival; ``held_share`` of the requests were held at all),
  ``pt_wait`` (to ``t_scheduled``, the first prompt grant), ``prefill``
  (to ``t_first_sampled``) and ``lag`` (to the first visible token: the
  device queue ahead of it, and the readback ring);
- ``tpot_p95_ms``; the run-ahead over the whole drive (engine counters);
- traced: the per-layer metrics in ``TRACED``, as their readers compute
  them, ``idle_gaps`` and ``idle_gaps_program`` (``program_trace.py``).

A program without the stamps or spans prints those parts as null.
"""
from __future__ import annotations

import argparse
import json
import shutil
from types import SimpleNamespace
from typing import List, Optional

from chipbench import program_trace, run, stats, trace_reduce, traffic, \
    weights

PARTS = ("late", "hold", "pt_wait", "prefill", "lag")
TRACED = ("sched_ms_per_iter.tpot", "device_idle_pct.tpot",
          "engine_sched_ms_per_iter.tpot", "drain_wait_p99_ms.tpot",
          "run_ahead_iters.tpot")


def parts(rec, gen) -> Optional[dict]:
    """TTFT of one finished request and its parts, in seconds; None where
    a stamp is missing."""
    t = [rec.due, gen.t_submit, getattr(gen, "t_admitted", None),
         getattr(gen, "t_scheduled", None),
         getattr(gen, "t_first_sampled", None), rec.stamps[0]]
    if any(x is None for x in t):
        return None
    out = {k: b - a for k, a, b in zip(PARTS, t, t[1:])}
    out["ttft"] = t[-1] - t[0]
    return out


def summary(rows: List[dict]) -> dict:
    """Median, p95 and mean of TTFT and of each part, in ms; the parts'
    means add up to the mean TTFT, their medians need not."""
    out = {}
    for k in ("ttft",) + PARTS:
        v = [r[k] for r in rows]
        out[k + "_p50_ms"] = stats.percentile(v, 50) * 1e3 if v else None
        out[k + "_p95_ms"] = stats.percentile(v, 95) * 1e3 if v else None
        out[k + "_mean_ms"] = sum(v) / len(v) * 1e3 if v else None
    if rows:
        out["parts_p50_sum_ms"] = sum(out[k + "_p50_ms"] for k in PARTS)
        out["held_share"] = sum(r["hold"] > 0 for r in rows) / len(rows)
    return out


def set_spans(on: bool) -> bool:
    """Set the engine's span switch; False where the program has none."""
    try:
        from repro.obs import spans
    except ImportError:
        return False
    spans.enabled = on
    return True


def measure(engine, cell, seed: int, seconds: float, tracer, comp,
            spans_on: bool = True) -> dict:
    """One drive of the cell's traffic from ``seed``, traced when
    ``tracer.enabled``; returns its line."""
    mix, vocab = cell.traffic, cell.config["vocab_size"]
    items = traffic.schedule(mix, seed, seconds, vocab)
    has_spans = set_spans(spans_on)
    tracer.plans.clear()
    if tracer.enabled:
        shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    ahead0 = (getattr(engine, "ahead_iters_sum", 0),
              getattr(engine, "n_ahead_samples", 0))
    comp.phase = "lead_in"
    recs, gens, _, _ = run.drive(
        engine, items, mix, seconds, tracer,
        float(mix.get("trace_s", run.DEFAULT_TRACE_S)), comp,
        log=lambda *a: None)
    while engine.has_work():
        engine.step()
    engine.flush()
    st = stats.steady(recs)
    rows = [parts(r, g) for r, g in zip(recs, gens)
            if r.segment == "window" and r.done]
    n_ahead = getattr(engine, "n_ahead_samples", 0) - ahead0[1]
    line = {"seed": seed, "spans": spans_on if has_spans else None,
            "window_requests": st["attempted"], "failed": st["failed"],
            "tpot_p95_ms": stats.finite(st["tpot_p95_s"]) * 1e3,
            "compiles_in_window": comp.of("window", "compiles"),
            "run_ahead_iters_drive": (
                (engine.ahead_iters_sum - ahead0[0]) / n_ahead
                if n_ahead else None)}
    line.update(summary([r for r in rows if r is not None]))
    if tracer.enabled:
        ctx = SimpleNamespace(
            trace=trace_reduce.reduce(run.TRACE_DIR, run.SPANS))
        line.update({m: run.reader(m)(ctx) for m in TRACED})
        line["idle_gaps"] = trace_reduce.top(ctx.trace.idle_by_span)
        line["idle_gaps_program"] = trace_reduce.top(
            program_trace.of(ctx).idle_by_span)
    comp.counts.clear()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--spans", default="1")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seconds", type=float, default=None)
    a = ap.parse_args(argv)
    import jax
    from repro.launch.serve import build_server
    cell = run.load_cell(a.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.say(f"ttft_parts needs a TPU, found {dev.platform}")
        return 2
    run.use_checkout_cache()
    comp = run.Compiles()
    config, mix = cell.config, cell.traffic
    seeds = [int(s) for s in a.seeds.split(",")]
    settings = [bool(int(s)) for s in a.spans.split(",")]
    seconds = a.seconds or float(mix.get("trace_s", run.DEFAULT_TRACE_S))
    args, cfg = run.program_config(config)
    params = weights.make(config, traffic.derived_seed(seeds[0], 1), dev)
    engine = build_server(cfg, args, params)
    vocab = config["vocab_size"]
    run.warm(engine, mix, traffic.schedule(mix, seeds[0], seconds, vocab),
             vocab)
    tracer = run.Tracer(enabled=bool(a.trace))
    run.instrument(engine, tracer)
    for k, seed in enumerate(seeds):
        for on in settings[::-1] if k % 2 else settings:
            print(json.dumps(measure(engine, cell, seed, seconds, tracer,
                                     comp, on)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
