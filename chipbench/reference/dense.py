"""Plain float32 forward pass of a dense GQA decoder (Qwen3, Mistral-Nemo).

Written from the published architecture, not from the program: RMSNorm
before attention and MLP, optional per-head RMSNorm of q and k (Qwen3),
rotary embedding on the two halves of each head, grouped-query attention
(query head h reads K/V head h // (heads / kv_heads)), causal softmax,
SwiGLU MLP, a final RMSNorm and an untied output head. No cache, no
batching, no kernels: one sequence at a time, layer by layer, so that only
one layer's weights are ever held in float32. Every matrix product runs at
``Precision.HIGHEST``.

``quant=True`` is the control: the same pass with every linear layer's
inputs (activations per row, weights per output column) rounded through
float8 e4m3 with absmax scaling, accumulating in float32 - the precision
step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
SEQ_BLOCK = 256
VOCAB_BLOCK = 32768


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    """a (..., K) @ w (K, N) in float32."""
    w = w.astype(jnp.float32)
    if quant:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x (S, n, hd), position = row index."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, consts, quant):
    H, K, hd, eps, theta, qk_norm = consts
    S = x.shape[0]
    h = _rms(x, w["norm1"], eps)
    q = _mm(h, w["wq"], quant).reshape(S, H, hd)
    k = _mm(h, w["wk"], quant).reshape(S, K, hd)
    v = _mm(h, w["wv"], quant).reshape(S, K, hd)
    if qk_norm:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    g = H // K
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, H * hd)
    x = x + _mm(o, w["wo"], quant)
    h = _rms(x, w["norm2"], eps)
    y = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(y, w["w_down"], quant)


_LAYER_LEAVES = {"norm1": "A/norm1", "norm2": "A/norm2", "wq": "A/attn/wq",
                 "wk": "A/attn/wk", "wv": "A/attn/wv", "wo": "A/attn/wo",
                 "w_gate": "A/mlp/w_gate", "w_up": "A/mlp/w_up",
                 "w_down": "A/mlp/w_down", "q_norm": "A/attn/q_norm",
                 "k_norm": "A/attn/k_norm"}


@functools.partial(jax.jit, static_argnums=(2,))
def _final(x, w, eps):
    return _rms(x, w, eps)


def hidden(weights: Dict[str, jax.Array], config: dict,
           tokens: Sequence[int], quant: bool = False) -> jax.Array:
    """Final normed hidden states (S, d), float32, of one sequence."""
    S = len(tokens)
    Sb = -(-S // SEQ_BLOCK) * SEQ_BLOCK          # causal: tail pad is inert
    toks = np.zeros(Sb, np.int32)
    toks[:S] = tokens
    x = jnp.take(weights["embed/tok"], jnp.asarray(toks), axis=0
                 ).astype(jnp.float32)
    consts = (config["num_attention_heads"], config["num_key_value_heads"],
              config["head_dim"], float(config["rms_norm_eps"]),
              float(config["rope_theta"]), bool(config.get("qk_norm")))
    for layer in range(config["num_hidden_layers"]):
        w = {k: weights[v][layer] for k, v in _LAYER_LEAVES.items()
             if v in weights}
        x = _layer(x, w, consts, quant)
    return _final(x, weights["final_norm"], float(config["rms_norm_eps"]))[:S]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_block(h, head, start, size, quant):
    blk = jax.lax.dynamic_slice_in_dim(head, start, size, axis=1)
    logits = _mm(h, blk, quant)
    return jnp.max(logits, -1), jnp.argmax(logits, -1) + start


@jax.jit
def _token_logit(h, head, tokens):
    cols = jnp.take(head, tokens, axis=1).astype(jnp.float32)   # (d, P)
    return jnp.einsum("pd,dp->p", h, cols, precision=HI)


def best(weights, h: jax.Array, quant: bool = False):
    """Per row of ``h``: (largest logit, its token), over the whole
    vocabulary, computed in vocabulary blocks."""
    head = weights["head"]
    V = head.shape[1]
    size = min(VOCAB_BLOCK, V)
    mx = am = None
    for start in range(0, V, size):
        s = min(start, V - size)
        m, a = _head_block(h, head, s, size, quant)
        if mx is None:
            mx, am = m, a
        else:
            am = jnp.where(m > mx, a, am)
            mx = jnp.maximum(m, mx)
    return mx, am


def served_gaps(weights, config: dict, prompt: Sequence[int],
                served: Sequence[int], control: bool = False) -> np.ndarray:
    """For each served token: how far the reference's logit of the token
    lies below the reference's largest logit at that position. With
    ``control``, the tokens judged are the ones the float8 pass puts first
    at each position of the same prompt and served tokens."""
    seq = list(prompt) + list(served[:-1])
    P = len(prompt)
    h = hidden(weights, config, seq)[P - 1:]
    mx, _ = best(weights, h)
    if control:
        hq = hidden(weights, config, seq, quant=True)[P - 1:]
        _, toks = best(weights, hq, quant=True)
    else:
        toks = jnp.asarray(np.asarray(served, np.int32))
    got = _token_logit(h, weights["head"], toks)
    return np.asarray(mx - got)
