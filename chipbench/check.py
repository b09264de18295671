"""Whether the timed path served the right tokens.

Once the window has closed and the server is gone, a sample of the
requests it finished is drawn from the seed: the longest of them, then
others in a seeded order until ``check.served_tokens`` served tokens are
in. The plain float32 reference (``reference/dense.py``) runs once over
each sampled prompt with its served tokens, and the number compared is
the widest gap by which a served token's reference logit lies below the
reference's largest logit at that position. Greedy decoding serves the
argmax of the program's own bfloat16 logits, so a sound run reads a gap
of bfloat16 rounding; a wrong token reads a gap of the logits' spread.

The configuration file states the limit (``check.logit_gap_limit``) and
the traffic file the number of served tokens compared; ``PERF.md`` gives
the readings each limit was set from.
"""
from __future__ import annotations

import time
from typing import List, Sequence, Tuple

import numpy as np

from chipbench import traffic
from chipbench.reference import dense


def sample(served: Sequence[Tuple[list, list, object]], seed: int,
           tokens: int) -> List[Tuple[list, list]]:
    """The longest finished request, then others in a seeded order until
    ``tokens`` served tokens are in the sample."""
    if not served:
        return []
    by_len = max(range(len(served)),
                 key=lambda i: (len(served[i][0]) + len(served[i][1]), -i))
    rng = np.random.default_rng(traffic.seed_words(seed).spawn(1)[0])
    order = [by_len] + [i for i in rng.permutation(len(served))
                        if i != by_len]
    out, n = [], 0
    for i in order:
        if n >= tokens:
            break
        out.append((served[i][0], served[i][1]))
        n += len(served[i][1])
    return out


def widest_gap(weights, config: dict, picked, control: bool = False
               ) -> float:
    return max(float(np.max(dense.served_gaps(weights, config, p, s,
                                              control=control)))
               for p, s in picked)


def verify(weights, config: dict, mix: dict, seed: int, served, log,
           control: bool = False) -> dict:
    """``correct`` and each compared number beside its limit."""
    want = int(mix["check"]["served_tokens"])
    limit = float(config["check"]["logit_gap_limit"])
    t0 = time.perf_counter()
    picked = sample(served, seed, want)
    n = sum(len(s) for _, s in picked)
    gap = widest_gap(weights, config, picked) if picked else float("inf")
    log(f"reference: {len(picked)} requests, {n} served tokens, "
        f"{time.perf_counter() - t0:.3f} s")
    numbers = {"logit_gap": {"value": gap, "limit": limit},
               "tokens_compared": {"value": n, "limit": want}}
    ok = gap <= limit and n >= want
    if control and picked:
        numbers["control_gap"] = {
            "value": widest_gap(weights, config, picked, control=True),
            "limit": limit}
    return {"correct": bool(ok), "numbers": numbers}
