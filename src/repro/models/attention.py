"""GQA attention with RoPE, optional qk-norm and sliding window.

Two entry points:
  * ``attn_prefill`` — full-sequence causal attention, returns the layer
    output plus the K/V tensors to seed a cache.
  * ``attn_decode``  — one new token against a (possibly ring-buffer) cache.

The default math path is pure jnp (the oracle the Pallas kernels are tested
against); ``impl='pallas'`` routes the core attention through
``repro.kernels.ops`` on CPU via interpret mode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (HEADS, KV, EMBED, NUL, ParamMeta, ParamTree, apply_rope,
                     rms_norm, softcap)
from .config import ModelConfig

NEG_INF = -1e30
# sequences longer than this use the streaming jnp flash path in the XLA
# implementation (the dense S^2 path is kept for short-seq tests/decode)
FLASH_THRESHOLD = 2048


def attn_params(cfg: ModelConfig, *, kv_heads: Optional[int] = None) -> ParamTree:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh = cfg.num_heads
    nkv = kv_heads or cfg.num_kv_heads
    t: ParamTree = {
        "wq": ParamMeta((d, nh * hd), (EMBED, HEADS)),
        "wk": ParamMeta((d, nkv * hd), (EMBED, KV)),
        "wv": ParamMeta((d, nkv * hd), (EMBED, KV)),
        "wo": ParamMeta((nh * hd, d), (HEADS, EMBED)),
    }
    if cfg.use_qk_norm:
        t["q_norm"] = ParamMeta((hd,), (NUL,), init="ones")
        t["k_norm"] = ParamMeta((hd,), (NUL,), init="ones")
    return t


def _project_qkv(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, nkv: int):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, nkv, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, nkv, hd)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _flash_jnp(q: jax.Array, k: jax.Array, v: jax.Array,
               pos_q: jax.Array, pos_k: jax.Array, cfg: ModelConfig,
               block_q: int = 512, block_k: int = 1024,
               segment_ids: Optional[jax.Array] = None,
               kv_segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Streaming (flash-style) attention in pure jnp: double lax.scan with
    online softmax — O(S) memory instead of the S^2 logits tensor, and the
    q-block body is rematerialized in the backward pass. This is the XLA
    fallback for long sequences; the Pallas kernel is the TPU fast path.

    ``segment_ids`` (B, S) restricts attention to equal segments (token-
    packed prefill: a block-diagonal mask over concatenated prompts).
    ``kv_segment_ids`` (B, Sk) gives the key axis its own segment array
    (packed multi-request chunked prefill, where the key axis carries
    several requests' prefix views plus their chunks).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    G = H // K
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    seg_q = segment_ids
    seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        pos_q = jnp.pad(pos_q, ((0, 0), (0, pq)), constant_values=-1)
        if seg_q is not None:       # -1/-2: pad q never matches any pad k
            seg_q = jnp.pad(seg_q, ((0, 0), (0, pq)), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        pos_k = jnp.pad(pos_k, ((0, 0), (0, pk)), constant_values=2**30)
        if seg_k is not None:
            seg_k = jnp.pad(seg_k, ((0, 0), (0, pk)), constant_values=-2)
    nq, nk = q.shape[1] // bq, k.shape[1] // bk
    packed = seg_q is not None
    qs = jnp.moveaxis(q.reshape(B, nq, bq, K, G, hd), 1, 0)
    pqs = jnp.moveaxis(pos_q.reshape(B, nq, bq), 1, 0)
    ks = jnp.moveaxis(k.reshape(B, nk, bk, K, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nk, bk, K, hd), 1, 0)
    pks = jnp.moveaxis(pos_k.reshape(B, nk, bk), 1, 0)
    if packed:
        sqs = jnp.moveaxis(seg_q.reshape(B, nq, bq), 1, 0)
        sks = jnp.moveaxis(seg_k.reshape(B, nk, bk), 1, 0)
    else:       # the scan operand structure must be static either way
        sqs = jnp.zeros((nq, B, 0), jnp.int32)
        sks = jnp.zeros((nk, B, 0), jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    def q_step(_, inp):
        qi, pqi, sqi = inp                          # (B,bq,K,G,hd), (B,bq)

        def k_step(carry, inp2):
            m, l, acc = carry
            kj, vj, pkj, skj = inp2
            s = jnp.einsum("bskgh,btkh->bkgst", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            s = softcap(s, cfg.attn_logit_softcap)
            ii = pqi[:, None, None, :, None]
            jj = pkj[:, None, None, None, :]
            mask = jj <= ii
            if cfg.sliding_window is not None:
                mask &= jj > ii - cfg.sliding_window
            if packed:      # block-diagonal (token-packed) masking only
                mask &= (sqi[:, None, None, :, None]
                         == skj[:, None, None, None, :])
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkh->bkgsh", p, vj.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, bq), jnp.float32)
        a0 = jnp.zeros((B, K, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0),
                                      (ks, vs, pks, sks))
        o = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,K,G,bq,hd)
        return None, jnp.moveaxis(o, 3, 1)          # (B,bq,K,G,hd)

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None, (qs, pqs, sqs))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, q.shape[1], H, hd)
    return out[:, :Sq].astype(q.dtype)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
          cfg: ModelConfig) -> jax.Array:
    """q (B,Sq,H,hd), k/v (B,Sk,K,hd), mask (B,Sq,Sk) or (1,Sq,Sk) bool."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    # keep K/V in their storage dtype: the MXU multiplies bf16 natively with
    # fp32 accumulation — upcasting the whole cache would double its HBM
    # traffic (decode roofline iteration 1, EXPERIMENTS.md §Perf)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) \
        / jnp.sqrt(hd).astype(jnp.float32)
    logits = softcap(logits, cfg.attn_logit_softcap)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


POS_INVALID = 2 ** 30           # mirrors kernels.flash_prefill.POS_INVALID


def attn_prefill(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                 *, segment_ids: Optional[jax.Array] = None,
                 kv_heads: Optional[int] = None, impl: str = "xla",
                 prefix_k: Optional[jax.Array] = None,
                 prefix_v: Optional[jax.Array] = None,
                 prefix_len: Optional[jax.Array] = None,
                 prefix_positions: Optional[jax.Array] = None,
                 prefix_segment_ids: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """``segment_ids`` (B, S) enables token-packed prefill: several prompts
    concatenated along the sequence axis attend block-diagonally (equal
    segment only), with ``positions`` restarting per segment.

    ``prefix_k``/``prefix_v`` (B, C, K, hd) + ``prefix_len`` (scalar)
    enable chunked prefill: the chunk queries attend over the first
    ``prefix_len`` slots of an already-seeded cache row (identity
    placement — token p at slot p, already RoPE'd) and then causally over
    the chunk itself, whose ``positions`` are absolute (offset by the
    prefix). Returns only the *chunk's* K/V for seeding.

    Packed multi-request chunked prefill combines both: ``segment_ids``
    marks each chunk's tokens, ``prefix_k``/``prefix_v`` concatenate the
    requests' cache-prefix views along the key axis, and
    ``prefix_positions``/``prefix_segment_ids`` (B, C) replace the
    scalar ``prefix_len`` — per-prefix-slot positions (``POS_INVALID``
    beyond each request's seeded prefix) and owning segment ids. Every
    chunk then attends over its own prefix view plus itself, block-
    diagonally, in ONE rectangular call.
    """
    B, S, _ = x.shape
    nkv = kv_heads or cfg.num_kv_heads
    q, k, v = _project_qkv(p, cfg, x, positions, nkv)
    if prefix_k is not None:
        # chunk continuation: key axis = seeded cache-prefix view (slots
        # [0, prefix_len) hold already-RoPE'd K at identity positions)
        # concatenated with the chunk; invalid prefix slots carry the
        # POS_INVALID sentinel, which causality masks
        C = prefix_k.shape[1]
        if prefix_positions is not None:
            kpos_prefix = jnp.broadcast_to(prefix_positions, (B, C))
        else:
            slot = jnp.arange(C)
            kpos_prefix = jnp.broadcast_to(
                jnp.where(slot < prefix_len, slot, POS_INVALID)[None],
                (B, C))
        kpos = jnp.concatenate([kpos_prefix, positions], axis=1)
        kseg = None
        if segment_ids is not None:
            kseg = jnp.concatenate(
                [jnp.broadcast_to(prefix_segment_ids, (B, C)), segment_ids],
                axis=1)
        k_all = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
        if impl == "pallas":
            from repro.kernels import ops
            out = ops.flash_attention(q, k_all, v_all, segment_ids,
                                      positions, kpos, kseg, causal=True,
                                      window=cfg.sliding_window,
                                      softcap=cfg.attn_logit_softcap)
        elif C + S > FLASH_THRESHOLD:
            out = _flash_jnp(q, k_all, v_all, positions, kpos, cfg,
                             segment_ids=segment_ids, kv_segment_ids=kseg)
        else:
            ii = positions[:, :, None]  # query positions (B,S,1)
            jj = kpos[:, None, :]       # key positions (B,1,C+S)
            mask = jj <= ii
            if cfg.sliding_window is not None:
                mask &= jj > ii - cfg.sliding_window
            if kseg is not None:
                mask &= segment_ids[:, :, None] == kseg[:, None, :]
            out = _sdpa(q, k_all, v_all, mask, cfg)
    elif impl == "pallas":
        from repro.kernels import ops
        out = ops.flash_attention(q, k, v, causal=True,
                                  window=cfg.sliding_window,
                                  softcap=cfg.attn_logit_softcap,
                                  segment_ids=segment_ids)
    elif S > FLASH_THRESHOLD:
        out = _flash_jnp(q, k, v, positions, positions, cfg,
                         segment_ids=segment_ids)
    else:
        ii = positions[:, :, None]  # query positions (B,S,1)
        jj = positions[:, None, :]  # key positions (B,1,S)
        mask = jj <= ii
        if cfg.sliding_window is not None:
            mask &= jj > ii - cfg.sliding_window
        if segment_ids is not None:
            mask &= segment_ids[:, :, None] == segment_ids[:, None, :]
        out = _sdpa(q, k, v, mask, cfg)
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1), p["wo"])
    return y, (k, v)


def attn_decode(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                cache_k: jax.Array, cache_v: jax.Array, layer,
                active: jax.Array, *, kv_heads: Optional[int] = None,
                impl: str = "xla"
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode against layer ``layer`` of a stacked cache.

    x (B,1,d); pos (B,) absolute position of the new token; active (B,)
    bool; cache_k/v (L, B, C, K, hd) where C = full context or sliding
    window size. The new K/V row is written in place at
    ``[layer, b, slot_b]`` for active rows only (inactive rows target an
    out-of-range slot and are dropped), then the query attends over
    ``cache[layer]`` (the Pallas kernel reads that layer's pages out of
    the stack itself). Returns (y (B,1,d), updated stacked cache).
    """
    B = x.shape[0]
    C = cache_k.shape[2]
    nkv = kv_heads or cfg.num_kv_heads
    q, k, v = _project_qkv(p, cfg, x, pos[:, None], nkv)

    windowed = cfg.sliding_window is not None and C == cfg.sliding_window
    slot = jnp.where(windowed, pos % C, jnp.minimum(pos, C - 1))
    bidx = jnp.arange(B)
    wslot = jnp.where(active, slot, C)
    cache_k = cache_k.at[layer, bidx, wslot].set(k[:, 0], mode="drop")
    cache_v = cache_v.at[layer, bidx, wslot].set(v[:, 0], mode="drop")

    idx = jnp.arange(C)[None, :]                     # (1,C) slot index
    if windowed:
        # ring buffer: slot i holds the token `age = (slot - i) mod C` steps
        # back; valid iff that token has been written (age <= pos).
        age = jnp.mod(slot[:, None] - idx, C)
        mask = age <= pos[:, None]
    else:
        mask = idx <= slot[:, None]
    if impl == "pallas":
        from repro.kernels import ops
        # every written slot is valid; softmax is permutation-invariant, so
        # ring-buffer slot order does not matter — a count suffices
        n_valid = jnp.minimum(pos + 1, C) if windowed else pos + 1
        out = ops.decode_attention(q[:, 0], cache_k, cache_v, n_valid,
                                   layer, softcap=cfg.attn_logit_softcap
                                   )[:, None]
    else:
        out = _sdpa(q, cache_k[layer], cache_v[layer], mask[:, None, :],
                    cfg)
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, -1), p["wo"])
    return y, (cache_k, cache_v)
