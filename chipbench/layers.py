"""Shared arithmetic of the per-layer metric readers.

Programs are told apart by the jitted function's name in the module
events of the trace (``jit_<name>(<id>)``): the decode programs are the
engine's fused decode step and its megastep loop; the prefill programs are
the prompt passes (packed, padded, chunked) and the cache-seeding scatters
and first-token sampling that go with them.
"""
from __future__ import annotations

import re
from typing import Iterable, Tuple

from chipbench import work

DECODE = ("_one_iter", "_mega_fn")
PREFILL = ("_prefill_fn", "_prefill_packed_fn", "_chunk_fn",
           "_chunks_packed_fn", "_rec_chunk_fn", "_seed_fn",
           "_seed_packed_fn", "_seed_slots_fn", "sample_in_graph")
_NAME = re.compile(r"^jit_(.+?)(\(-?\d+\))?$")


def program(module: str) -> str:
    m = _NAME.match(module)
    return m.group(1) if m else module


def device_s(trace, names: Iterable[str]) -> float:
    names = set(names)
    return sum(v for k, v in trace.module_s.items() if program(k) in names)


def decode_iters(plans) -> list:
    return [c for c, _ in plans if c]


def prefill_waves(plans) -> list:
    return [p for _, p in plans if p]


def decode_work(ctx) -> Tuple[float, float, float]:
    """(flops, least seconds, iterations) of the traced decode iterations."""
    f = t = 0.0
    iters = decode_iters(ctx.plans)
    for contexts in iters:
        fl, by = work.decode_iteration(ctx.dims, contexts)
        f += fl
        t += work.least_time(fl, by, ctx.peak)
    return f, t, float(len(iters))


def prefill_work(ctx) -> Tuple[float, float, float]:
    """(flops, least seconds, prompt tokens) of the traced prefill waves."""
    f = t = n = 0.0
    for items in prefill_waves(ctx.plans):
        fl, by = work.prefill_wave(ctx.dims, items)
        f += fl
        t += work.least_time(fl, by, ctx.peak)
        n += sum(c for c, _, _ in items)
    return f, t, n
