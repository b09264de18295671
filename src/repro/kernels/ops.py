"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels run in interpret mode, which executes the
kernel body in Python — bit-accurate for correctness tests, not for
speed. On TPU the same code lowers to Mosaic. Any other backend is an
error: the kernels are written for the TPU and are never silently
interpreted there.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_prefill import flash_attention as _flash_pallas
from .paged_attention import paged_decode_attention as _paged_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on TPU (or interpreted on "
                           f"CPU), not on backend {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, segment_ids=None, q_positions=None,
                    kv_positions=None, kv_segment_ids=None, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    """Prefill/training attention. q (B,Sq,H,hd); k/v (B,Sk,K,hd).

    ``segment_ids`` (B,S) int32 (optional) makes the mask block-diagonal —
    the token-packed prefill path, where a wave of prompts runs as one
    flattened sequence with no batch- or length-padding.

    ``q_positions`` (B,Sq) / ``kv_positions`` (B,Sk) (optional, together)
    switch to explicit-position masking and allow Sq != Sk — the
    chunked-prefill path, where the key axis is a seeded cache-prefix view
    concatenated with the chunk (invalid prefix slots carry
    ``flash_prefill.POS_INVALID``).

    ``kv_segment_ids`` (B,Sk) (optional, with ``segment_ids``) gives the
    key axis its own segment array — the packed multi-request chunk path,
    where several requests' prefix views plus their packed chunks share
    one call."""
    bq = min(block_q, max(16, q.shape[1]))
    bk = min(block_k, max(16, k.shape[1]))
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         softcap=softcap, segment_ids=segment_ids,
                         kv_segment_ids=kv_segment_ids,
                         q_positions=q_positions, kv_positions=kv_positions,
                         block_q=bq, block_k=bk,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("softcap", "pages_per_step"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens,
                           *, softcap: Optional[float] = None,
                           pages_per_step: int = 8):
    """Decode attention over an explicitly paged cache."""
    return _paged_pallas(q, k_pages, v_pages, block_tables, context_lens,
                         softcap=softcap, pages_per_step=pages_per_step,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("softcap", "pages_per_step"))
def decode_attention(q, cache_k, cache_v, context_lens, layer=0, *,
                     softcap: Optional[float] = None,
                     pages_per_step: int = 8):
    """Decode attention over a contiguous per-request cache row.

    q (B,H,hd); cache_k/v (B,C,K,hd), or a layer stack (L,B,C,K,hd) of
    which layer ``layer`` is read; context_lens (B,) — number of valid
    slots (for ring buffers every written slot is valid; softmax is
    permutation-invariant so slot order does not matter).

    Implemented by viewing each row as pages of the paged kernel. A stack
    is viewed as pages whole and the layer picked by the block table, so
    no layer is sliced out of it.
    """
    B, C, K, hd = cache_k.shape[-4:]
    for ps in (128, 64, 32, 16, 8):
        if C % ps == 0:
            break
    else:
        ps = C
    mp = C // ps
    kp = cache_k.reshape(-1, ps, K, hd)
    vp = cache_v.reshape(-1, ps, K, hd)
    bt = (layer * (B * mp) + jnp.arange(B)[:, None] * mp
          + jnp.arange(mp)[None, :]).astype(jnp.int32)
    return _paged_pallas(q, kp, vp, bt, context_lens.astype(jnp.int32),
                         softcap=softcap, pages_per_step=pages_per_step,
                         interpret=_interpret())


# re-export oracles for convenience
flash_attention_ref = ref.flash_attention
paged_decode_attention_ref = ref.paged_decode_attention
kv_page_append = jax.jit(ref.kv_page_append)
