"""Operations and bytes of the serving steps, counted from shapes.

The counts are of the work the algorithm needs, whatever implements it:

- the weights are read once per program call (all layers and the output
  head; the embedding is a gather of a few rows and is left out, as are
  the norm vectors);
- attention reads the K/V of the *live* context only, not the capacity of
  the cache row, and writes the new tokens' K/V;
- a decode iteration attends each sequence over its live context (the new
  token included) and projects one row of logits per sequence;
- a prefill wave runs each prompt chunk through every layer, attends it
  causally within the chunk and over its already-cached prefix (per
  segment: no attention across packed prompts, none to padding) and
  projects logits only for the chunks that finish their prompt.

Activations are not counted. A multiply-add is two operations.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple

BYTES = 2   # bfloat16 weights and cache


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int

    @staticmethod
    def of(config: dict) -> "Dims":
        return Dims(config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"],
                    config["intermediate_size"], config["vocab_size"],
                    config["num_hidden_layers"])

    @property
    def layer_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + 3 * self.d * self.ff

    @property
    def weight_bytes(self) -> int:
        return (self.layers * self.layer_params + self.d * self.vocab) * BYTES

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * BYTES

    def attn_flops(self, pairs: float) -> float:
        """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
        return self.layers * 4.0 * self.heads * self.head_dim * pairs

    def token_flops(self, n: int) -> float:
        """Linear layers of ``n`` tokens through every layer."""
        return 2.0 * n * self.layers * self.layer_params

    def head_flops(self, n: int) -> float:
        return 2.0 * n * self.d * self.vocab


def decode_iteration(dims: Dims, contexts: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode iteration; ``contexts`` holds, per
    sequence, the number of tokens it attends (the new one included)."""
    b, live = len(contexts), float(sum(contexts))
    flops = dims.token_flops(b) + dims.attn_flops(live) + dims.head_flops(b)
    nbytes = dims.weight_bytes + dims.kv_bytes_per_token * (live + b)
    return flops, nbytes


def prefill_wave(dims: Dims, items: Iterable[Tuple[int, int, bool]]
                 ) -> Tuple[float, float]:
    """(flops, bytes) of one prefill program call over ``items`` of
    (new tokens, cached prefix tokens, emits a first token)."""
    items = list(items)
    new = sum(n for n, _, _ in items)
    prefix = sum(p for _, p, _ in items)
    pairs = sum(n * p + n * (n + 1) / 2.0 for n, p, _ in items)
    emit = sum(1 for _, _, e in items if e)
    flops = dims.token_flops(new) + dims.attn_flops(pairs) \
        + dims.head_flops(emit)
    nbytes = dims.weight_bytes + dims.kv_bytes_per_token * (new + prefix)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def peaks(device_kind: str) -> dict:
    """The peak row for a device kind; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
