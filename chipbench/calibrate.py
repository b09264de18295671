"""Readings that the correctness limits are set from, in one process.

    python3 -m chipbench.calibrate --workload <cell> --seconds <s> \
        --seeds <n> --control <m> [--first-seed <k>]

Runs the cell ``n`` times with fresh seeds at its own load for a short
window, and prints for each the widest logit gap of the served tokens
(the lower reading is the largest of them); for the first ``m`` seeds it
also reads the float8 control on the same sample (the upper reading is the
smallest of those). One line of JSON per seed on standard output. The
benchmark's own runs never read the control.
"""
from __future__ import annotations

import argparse
import gc
import json

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    a = ap.parse_args(argv)
    cell = run.load_cell(a.workload)
    run.use_checkout_cache()
    for k in range(a.seeds):
        seed = a.first_seed + 7919 * k
        out = run.run_cell(cell, seed, a.seconds, False,
                           control=k < a.control)
        if out is None:
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "failed": out["failed"], "check": out["check"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
