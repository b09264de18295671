"""Stratified open-loop and batch traffic, read from a traffic data file.

Every seed offers the same multiset of work. Prompt and response lengths
are taken at stratified quantiles ``(i + 0.5) / n`` of clipped lognormals
(the Alpaca and ShareGPT parameters come from the repo's
``core/traces.py``); their pairing keeps a Gaussian-copula latent
correlation drawn from a fixed pairing seed, so the pairs themselves are
the same for every seed. Inter-arrival gaps are stratified quantiles of
the exponential, scaled so that each segment (lead-in, window, tail)
spans exactly its length. The seed only permutes the order of pairs and
of gaps, and draws the prompt token ids.

A steady mix is generated in three segments so that the requests due in
the measured window are a fixed set: ``lead_in_s`` before the window,
``seconds`` of window and ``tail_s`` after it (load keeps arriving while
the window's last requests finish). A batch mix is a backlog all due at
t = 0.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np
from statistics import NormalDist

PAIRING_SEED = 0x5EED


def seed_words(seed: int) -> np.random.SeedSequence:
    """A seed sequence from any whole number (benchmark seeds may exceed
    32 bits)."""
    return np.random.SeedSequence(abs(int(seed)))


def derived_seed(seed: int, salt: int) -> int:
    """A 31-bit seed for consumers (such as ``jax.random.PRNGKey``) that
    keep only 32 bits of what they are given."""
    ss = np.random.SeedSequence([abs(int(seed)), salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


@dataclass(frozen=True)
class Lengths:
    mean: float
    sigma: float
    lo: int
    hi: int

    @staticmethod
    def of(d: dict) -> "Lengths":
        return Lengths(float(d["mean"]), float(d["sigma"]), int(d["min"]),
                       int(d["max"]))

    def quantiles(self, n: int) -> np.ndarray:
        """Sorted stratified quantiles of the clipped lognormal whose mean
        (before clipping) is ``mean``."""
        mu = math.log(self.mean) - 0.5 * self.sigma ** 2
        nd = NormalDist()
        z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        return np.clip(np.rint(np.exp(mu + self.sigma * z)), self.lo,
                       self.hi).astype(np.int64)


@dataclass
class Item:
    """One request of the schedule: due time (seconds from the start of
    the lead-in), prompt token ids, response length, and the segment it
    belongs to (``lead_in``, ``window`` or ``tail``)."""
    due: float
    prompt: List[int]
    out_len: int
    segment: str


def pairs(n: int, prompt: Lengths, output: Lengths,
          rl_corr: float) -> Tuple[np.ndarray, np.ndarray]:
    """n (prompt, response) length pairs, the same for every seed: the
    response quantiles are assigned by the rank of a latent that mixes the
    standardised log prompt length with fixed noise."""
    p = prompt.quantiles(n)
    o = output.quantiles(n)
    lp = np.log(p.astype(np.float64))
    z = (lp - lp.mean()) / (lp.std() + 1e-9)
    eps = np.random.default_rng([PAIRING_SEED, n]).normal(size=n)
    mix = rl_corr * z + math.sqrt(1.0 - rl_corr ** 2) * eps
    out = np.empty(n, np.int64)
    out[np.argsort(mix, kind="stable")] = o
    return p, out


def gaps(n: int, span: float) -> np.ndarray:
    """Stratified exponential quantiles, scaled to sum to ``span``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (span / g.sum())


def _segment(name: str, start: float, span: float, n: int, spec: dict,
             rng: np.random.Generator, vocab: int) -> List[Item]:
    if n <= 0:
        return []
    p, o = pairs(n, Lengths.of(spec["prompt"]), Lengths.of(spec["output"]),
                 float(spec.get("rl_corr", 0.45)))
    order = rng.permutation(n)
    p, o = p[order], o[order]
    if span > 0:
        due = start + np.cumsum(rng.permutation(gaps(n, span)))
    else:
        due = np.full(n, start)
    return [Item(float(due[i]),
                 rng.integers(0, vocab, int(p[i])).tolist(), int(o[i]), name)
            for i in range(n)]


def schedule(spec: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The whole run's requests in due order. ``spec`` is a traffic file."""
    rng = np.random.default_rng(seed_words(seed))
    lead = float(spec["lead_in_s"])
    if spec["kind"] == "batch":
        return _segment("window", 0.0, 0.0, int(spec["backlog"]), spec, rng,
                        vocab)
    rate = float(spec["rate"])
    tail = float(spec["tail_s"])
    items = []
    for name, start, span in (("lead_in", 0.0, lead),
                              ("window", lead, seconds),
                              ("tail", lead + seconds, tail)):
        items += _segment(name, start, span, int(round(rate * span)), spec,
                          rng, vocab)
    items.sort(key=lambda it: it.due)
    return items

