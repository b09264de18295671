"""Seeded weights for a dense GQA transformer, made by the benchmark.

The benchmark makes the weights itself, on the device, in one jitted call
from the seed and in the type they are served in, and hands them to the
program; the plain reference reads the same arrays. The dict uses the
serving engine's parameter names and layer-stacked shapes (the one thing
the benchmark must know of the program's layout).

Matrices are normal with standard deviation 1/sqrt(fan-in); the embedding
is standard normal; every norm scale is 1 + 0.1 * normal, so that a path
which drops a norm's scale does not agree with the reference.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Spec = Tuple[Tuple[str, Tuple[int, ...], str], ...]
# The TPU's own bit generator: a draw of the 16-layer weights takes seconds
# where threefry takes half a minute, at the cost of one leaf's bits as a
# temporary (3.2 GB for Qwen3-8B's stacked 4096 x 12288 projections).
IMPL = "rbg"


def spec(config: dict) -> Spec:
    """(name, shape, kind) of every leaf; kind is ``mat`` (fan-in is the
    second-to-last dim), ``embed`` or ``norm``."""
    d, f, V = config["hidden_size"], config["intermediate_size"], \
        config["vocab_size"]
    H, K, hd = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    L = config["num_hidden_layers"]
    leaves = [("embed/tok", (V, d), "embed"),
              ("A/norm1", (L, d), "norm"), ("A/norm2", (L, d), "norm"),
              ("A/attn/wq", (L, d, H * hd), "mat"),
              ("A/attn/wk", (L, d, K * hd), "mat"),
              ("A/attn/wv", (L, d, K * hd), "mat"),
              ("A/attn/wo", (L, H * hd, d), "mat"),
              ("A/mlp/w_gate", (L, d, f), "mat"),
              ("A/mlp/w_up", (L, d, f), "mat"),
              ("A/mlp/w_down", (L, f, d), "mat"),
              ("final_norm", (d,), "norm"),
              ("head", (d, V), "mat")]
    if config.get("qk_norm"):
        leaves += [("A/attn/q_norm", (L, hd), "norm"),
                   ("A/attn/k_norm", (L, hd), "norm")]
    return tuple(leaves)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(seed, leaves: Spec, dtype, impl: str) -> Dict[str, jax.Array]:
    keys = jax.random.split(jax.random.key(seed, impl=impl), len(leaves))
    out = {}
    for k, (name, shape, kind) in zip(keys, leaves):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "norm":
            w = 1.0 + 0.1 * z
        elif kind == "embed":
            w = z
        else:
            w = z * (1.0 / float(shape[-2]) ** 0.5)
        out[name] = w.astype(dtype)
    return out


def make(config: dict, seed31: int, device=None) -> Dict[str, jax.Array]:
    """All weights of ``config`` from a 31-bit seed, on ``device``."""
    dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
    with jax.default_device(device or jax.devices()[0]):
        return _make(jnp.int32(seed31), spec(config), dtype, IMPL)
