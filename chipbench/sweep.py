"""Find a steady cell's knee once, by a sweep on the chip.

    python3 -m chipbench.sweep --workload <cell> --rates 4,5,6,7 \
        --seconds 20 [--seed <n>]

One process builds the cell's server once and offers the cell's traffic
at each rate in turn for a lead-in and ``--seconds`` of window, letting
the server go idle between rates. For each rate it prints the requests in
the system (due, not yet finished) at the start and at the end of the
window, the completions per second in the window, and the window's TTFT
and TPOT percentiles. The knee is the highest rate at which the backlog
does not grow across the window; the cell's rate is set at about 0.8 of
it, in its traffic file.
"""
from __future__ import annotations

import argparse
import json

from chipbench import run, stats, traffic, weights


def in_system(recs, t: float) -> int:
    return sum(r.due <= t and (r.t_end is None or r.t_end > t)
               for r in recs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4_000_000_000)
    a = ap.parse_args(argv)
    import jax
    from repro.launch.serve import build_server
    cell = run.load_cell(a.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.say(f"sweep needs a TPU, found {dev.platform}")
        return 2
    run.use_checkout_cache()
    comp = run.Compiles()
    config = cell.config
    args, cfg = run.program_config(config)
    params = weights.make(config, traffic.derived_seed(a.seed, 1), dev)
    engine = build_server(cfg, args, params)
    for rate in (float(r) for r in a.rates.split(",")):
        mix = dict(cell.traffic, rate=rate)
        items = traffic.schedule(mix, a.seed, a.seconds,
                                 config["vocab_size"])
        run.warm(engine, mix, items, config["vocab_size"])
        comp.phase = "lead_in"
        recs, _, (w0, w1), delta = run.drive(
            engine, items, mix, a.seconds, run.Tracer(), 0.0, comp)
        while engine.has_work():
            engine.step()
        engine.flush()
        st = stats.steady(recs)
        done_in = sum(r.t_end is not None and w0 <= r.t_end < w1
                      for r in recs)
        fin = [x for x in st["ttft_s"] if x != stats.INF]
        print(json.dumps({
            "rate": rate, "in_system_start": in_system(recs, w0),
            "in_system_end": in_system(recs, w1),
            "completed_per_s": done_in / (w1 - w0),
            "window_requests": st["attempted"], "failed": st["failed"],
            "ttft_p50_ms": stats.percentile(fin, 50) * 1e3 if fin else None,
            "ttft_p95_ms": stats.finite(st["ttft_p95_s"]) * 1e3,
            "tpot_p95_ms": stats.finite(st["tpot_p95_s"]) * 1e3,
            "compiles_in_window": delta.get("compiles_in_window",
                                            comp.of("window", "compiles")),
            "decode_dispatches_per_iter":
                delta["decode_dispatches"] / max(1, delta["decode_iters"])}),
            flush=True)
        comp.counts.clear()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
