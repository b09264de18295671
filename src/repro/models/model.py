"""Model assembly: pattern-driven block stacks with scanned homogeneous
segments, shared-attention (Zamba2-style) support, prefill/decode/train paths.

Params live in a flat dict ``{path: array}``. Layers of the same kind are
stacked along a leading LAYER axis and executed with ``lax.scan`` over
contiguous segments of the layer pattern — this keeps compile time sane for
80-layer models while supporting interleaved patterns.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention, mlp, moe, ssm, xlstm
from .common import (BATCH_AXES, EMBED, LAYER, NUL, VOCAB, ParamMeta,
                     ParamTree, abstract_params, init_params,
                     maybe_constrain, rms_norm)
from .config import ATTN, MAMBA, MLSTM, SLSTM, ModelConfig

Params = Dict[str, jax.Array]
Cache = Dict[str, Any]


# --------------------------------------------------------------------------- #
# parameter tree
# --------------------------------------------------------------------------- #
def _block_tree(cfg: ModelConfig, kind: str) -> ParamTree:
    """Per-layer (unstacked) parameter tree for one block kind."""
    d = cfg.d_model
    t: ParamTree = {}
    if kind == ATTN:
        t["norm1"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in attention.attn_params(cfg).items():
            t[f"attn/{k}"] = m
        t["norm2"] = ParamMeta((d,), (EMBED,), init="ones")
        if cfg.is_moe:
            for k, m in moe.moe_params(cfg).items():
                t[f"moe/{k}"] = m
            if cfg.moe_dense_residual:
                for k, m in mlp.mlp_params(cfg).items():
                    t[f"mlp/{k}"] = m
        else:
            for k, m in mlp.mlp_params(cfg).items():
                t[f"mlp/{k}"] = m
    elif kind == MAMBA:
        t["norm"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in ssm.ssm_params(cfg).items():
            t[f"ssm/{k}"] = m
    elif kind == SLSTM:
        t["norm"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in xlstm.slstm_params(cfg).items():
            t[f"cell/{k}"] = m
    elif kind == MLSTM:
        t["norm"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in xlstm.mlstm_params(cfg).items():
            t[f"cell/{k}"] = m
    else:
        raise ValueError(kind)
    return t


def segments(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """Contiguous same-kind runs of the pattern: (kind, offset_in_kind, len).

    ``offset_in_kind`` indexes into the stacked params of that kind.
    """
    pat = cfg.pattern()
    segs: List[Tuple[str, int, int]] = []
    counts: Dict[str, int] = {}
    i = 0
    while i < len(pat):
        j = i
        while j < len(pat) and pat[j] == pat[i]:
            j += 1
        k = pat[i]
        segs.append((k, counts.get(k, 0), j - i))
        counts[k] = counts.get(k, 0) + (j - i)
        i = j
    return segs


def kind_counts(cfg: ModelConfig) -> Dict[str, int]:
    c: Dict[str, int] = {}
    for ch in cfg.pattern():
        c[ch] = c.get(ch, 0) + 1
    return c


def num_shared_invocations(cfg: ModelConfig) -> int:
    if not cfg.shared_attention_every:
        return 0
    return cfg.num_layers // cfg.shared_attention_every


def param_tree(cfg: ModelConfig) -> ParamTree:
    d, v = cfg.d_model, cfg.vocab_size
    t: ParamTree = {"embed/tok": ParamMeta((v, d), (VOCAB, EMBED))}
    for kind, n in kind_counts(cfg).items():
        for k, m in _block_tree(cfg, kind).items():
            t[f"{kind}/{k}"] = ParamMeta((n,) + m.shape, (LAYER,) + m.axes,
                                         init=m.init, scale=m.scale)
    if num_shared_invocations(cfg):
        scfg = cfg if not cfg.shared_attn_kv_heads else cfg.with_(
            num_kv_heads=cfg.shared_attn_kv_heads)
        t["shared/norm1"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in attention.attn_params(scfg).items():
            t[f"shared/attn/{k}"] = m
        t["shared/norm2"] = ParamMeta((d,), (EMBED,), init="ones")
        for k, m in mlp.mlp_params(cfg).items():
            t[f"shared/mlp/{k}"] = m
    t["final_norm"] = ParamMeta((d,), (EMBED,), init="ones")
    if not cfg.tie_embeddings:
        t["head"] = ParamMeta((d, v), (EMBED, VOCAB))
    return t


def init(cfg: ModelConfig, key: jax.Array) -> Params:
    return init_params(param_tree(cfg), key, jnp.dtype(cfg.param_dtype))


def abstract(cfg: ModelConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    return abstract_params(param_tree(cfg), jnp.dtype(cfg.param_dtype))


def _sub(params: Params, prefix: str) -> Params:
    pl = len(prefix)
    return {k[pl:]: v for k, v in params.items() if k.startswith(prefix)}


def _constrain_acts(x: jax.Array) -> jax.Array:
    """Residual-stream sharding: batch over (pod,data); sequence over
    "model" (Megatron-style sequence parallelism) — without it the remat-
    saved per-layer activations are replicated across the model axis."""
    seq = "model" if x.shape[1] > 1 else None
    return maybe_constrain(x, BATCH_AXES, seq, None)


# --------------------------------------------------------------------------- #
# block application
# --------------------------------------------------------------------------- #
def _apply_block_prefill(cfg: ModelConfig, kind: str, p: Params, x, positions,
                         impl: str, segment_ids=None, prefix=None,
                         prefix_len=None, prefix_positions=None,
                         prefix_segment_ids=None):
    """Returns (x_out, cache_slice, aux). ``prefix`` is this layer's chunk
    resume point: for attention, the seeded cache row {'k','v'} the chunk
    attends over (with ``prefix_len`` or per-slot ``prefix_positions`` /
    ``prefix_segment_ids`` for the packed multi-request form); for
    recurrent kinds, the carried state snapshot the chunk continues
    from."""
    aux = jnp.zeros((), jnp.float32)
    if kind == ATTN:
        h = rms_norm(x, p["norm1"], cfg.rms_eps)
        y, (k, v) = attention.attn_prefill(
            _sub(p, "attn/"), cfg, h, positions,
            segment_ids=segment_ids, impl=impl,
            prefix_k=None if prefix is None else prefix["k"],
            prefix_v=None if prefix is None else prefix["v"],
            prefix_len=prefix_len, prefix_positions=prefix_positions,
            prefix_segment_ids=prefix_segment_ids)
        x = x + y
        h = rms_norm(x, p["norm2"], cfg.rms_eps)
        if cfg.is_moe:
            y, aux = moe.moe_apply(_sub(p, "moe/"), cfg, h)
            if cfg.moe_dense_residual:
                y = y + mlp.mlp_apply(_sub(p, "mlp/"), h)
        else:
            y = mlp.mlp_apply(_sub(p, "mlp/"), h)
        return x + y, {"k": k, "v": v}, aux
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    if kind == MAMBA:
        y, cache = ssm.ssm_prefill(_sub(p, "ssm/"), cfg, h, init=prefix)
    elif kind == MLSTM:
        y, cache = xlstm.mlstm_prefill(_sub(p, "cell/"), cfg, h, init=prefix)
    elif kind == SLSTM:
        y, cache = xlstm.slstm_prefill(_sub(p, "cell/"), cfg, h, init=prefix)
    else:
        raise ValueError(kind)
    return x + y, cache, aux


def _apply_block_decode(cfg: ModelConfig, kind: str, p: Params, x, pos,
                        cache, impl: str, layer=None, active=None):
    """Attention takes the kind's whole stacked cache and writes layer
    ``layer``'s row in place (active rows only); recurrent kinds take and
    return their own layer's state."""
    if kind == ATTN:
        h = rms_norm(x, p["norm1"], cfg.rms_eps)
        y, (ck, cv) = attention.attn_decode(_sub(p, "attn/"), cfg, h, pos,
                                            cache["k"], cache["v"], layer,
                                            active, impl=impl)
        x = x + y
        h = rms_norm(x, p["norm2"], cfg.rms_eps)
        if cfg.is_moe:
            y, _ = moe.moe_apply(_sub(p, "moe/"), cfg, h)
            if cfg.moe_dense_residual:
                y = y + mlp.mlp_apply(_sub(p, "mlp/"), h)
        else:
            y = mlp.mlp_apply(_sub(p, "mlp/"), h)
        return x + y, {"k": ck, "v": cv}
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    if kind == MAMBA:
        y, cache = ssm.ssm_decode(_sub(p, "ssm/"), cfg, h, cache)
    elif kind == MLSTM:
        y, cache = xlstm.mlstm_decode(_sub(p, "cell/"), cfg, h, cache)
    elif kind == SLSTM:
        y, cache = xlstm.slstm_decode(_sub(p, "cell/"), cfg, h, cache)
    else:
        raise ValueError(kind)
    return x + y, cache


def _shared_attn_prefill(cfg, params, x, positions, impl, segment_ids=None,
                         prefix=None, prefix_len=None, prefix_positions=None,
                         prefix_segment_ids=None):
    scfg = cfg if not cfg.shared_attn_kv_heads else cfg.with_(
        num_kv_heads=cfg.shared_attn_kv_heads)
    p = _sub(params, "shared/")
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    y, (k, v) = attention.attn_prefill(
        _sub(p, "attn/"), scfg, h, positions,
        segment_ids=segment_ids, kv_heads=scfg.num_kv_heads, impl=impl,
        prefix_k=None if prefix is None else prefix["k"],
        prefix_v=None if prefix is None else prefix["v"],
        prefix_len=prefix_len, prefix_positions=prefix_positions,
        prefix_segment_ids=prefix_segment_ids)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.rms_eps)
    return x + mlp.mlp_apply(_sub(p, "mlp/"), h), (k, v)


def _shared_attn_decode(cfg, params, x, pos, active, cache, i, impl):
    """Invocation ``i`` of the shared block against the stacked shared
    cache, written in place like ``attn_decode``."""
    scfg = cfg if not cfg.shared_attn_kv_heads else cfg.with_(
        num_kv_heads=cfg.shared_attn_kv_heads)
    p = _sub(params, "shared/")
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    y, (ck, cv) = attention.attn_decode(
        _sub(p, "attn/"), scfg, h, pos, cache["k"], cache["v"], i, active,
        kv_heads=scfg.num_kv_heads, impl=impl)
    x = x + y
    h = rms_norm(x, p["norm2"], cfg.rms_eps)
    return x + mlp.mlp_apply(_sub(p, "mlp/"), h), {"k": ck, "v": cv}


# --------------------------------------------------------------------------- #
# embeddings & logits
# --------------------------------------------------------------------------- #
def embed_inputs(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 embeds: Optional[jax.Array]) -> jax.Array:
    x = jnp.take(params["embed/tok"], tokens, axis=0).astype(cfg.dtype)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(cfg.dtype), x], axis=1)
    return x


def logits_fn(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed/tok"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("...d,dv->...v", x, head)
    # batch over (pod, data), vocab over model — keeps CE sharded
    return maybe_constrain(logits, BATCH_AXES,
                           *([None] * (logits.ndim - 2)), "model")


# --------------------------------------------------------------------------- #
# full passes
# --------------------------------------------------------------------------- #
def _run_stack(cfg: ModelConfig, params: Params, x: jax.Array,
               positions: jax.Array, impl: str,
               decode: bool = False, pos=None, caches: Optional[Cache] = None,
               active=None,
               segment_ids: Optional[jax.Array] = None,
               prefix_caches: Optional[Cache] = None, prefix_len=None,
               prefix_positions=None, prefix_segment_ids=None):
    """Shared driver for prefill (decode=False) and decode (decode=True).

    ``prefix_caches``/``prefix_len`` (prefill only): per-layer seeded cache
    rows a chunk's queries attend over (chunked prefill) — threaded through
    the layer scan exactly like decode threads its caches. The packed
    multi-request form replaces the scalar ``prefix_len`` with per-slot
    ``prefix_positions``/``prefix_segment_ids``; recurrent kinds instead
    receive their carried state snapshots through the same pytree.

    Decode carries each attention kind's whole stacked cache through the
    layer loop (never as scan ``xs``/``ys``, which would slice out and
    write back every layer's slab): each layer writes its new row in place
    and attends over its own layer of the carried cache. Recurrent kinds
    advance only the ``active`` rows of their (small) states.
    """
    aux_total = jnp.zeros((), jnp.float32)
    if decode:
        caches = dict(caches)
    new_caches: Dict[str, List] = {k: [] for k in cfg.block_kinds()}
    shared_caches: List = []
    every = cfg.shared_attention_every
    n_done = 0          # pattern layers consumed
    shared_i = 0

    for kind, off, length in segments(cfg):
        stacked = _sub(params, f"{kind}/")
        # split the segment at shared-attention insertion points
        sub_start = 0
        while sub_start < length:
            if every:
                upto = (n_done // every + 1) * every - n_done
                run = min(length - sub_start, upto)
            else:
                run = length - sub_start
            seg_params = jax.tree.map(
                lambda a: jax.lax.slice_in_dim(a, off + sub_start,
                                               off + sub_start + run, axis=0),
                stacked)
            # --- scan over the run (single-layer runs skip the scan: the
            # XLA while-loop wrapper costs real per-step overhead on the
            # decode hot path, and hybrid patterns produce many length-1
            # segments; the unrolled call is mathematically identical) ---
            x = _constrain_acts(x)
            if decode and kind == ATTN:
                def body_a(carry, lp):
                    xc, kv, layer = carry
                    y, kv = _apply_block_decode(cfg, kind, lp, xc, pos, kv,
                                                impl, layer, active)
                    return (y, kv, layer + 1), None

                body = jax.checkpoint(body_a) if cfg.remat else body_a
                start = off + sub_start
                if run == 1:
                    (x, caches[kind], _), _ = body(
                        (x, caches[kind], start),
                        jax.tree.map(lambda a: a[0], seg_params))
                else:
                    (x, caches[kind], _), _ = jax.lax.scan(
                        body, (x, caches[kind], jnp.int32(start)),
                        seg_params)
            elif decode:
                cache_off = _cache_offset(new_caches[kind])
                seg_cache = jax.tree.map(
                    lambda a: jax.lax.slice_in_dim(a, cache_off,
                                                   cache_off + run, axis=0),
                    caches[kind])

                def body_d(carry, xs):
                    xc = carry
                    lp, lc = xs
                    y, c2 = _apply_block_decode(cfg, kind, lp, xc, pos, lc,
                                                impl)
                    # an idle row keeps its state: a spurious h <- f(h, x)
                    # advance would corrupt it
                    c2 = jax.tree.map(
                        lambda old, new: jnp.where(active.reshape(
                            (-1,) + (1,) * (new.ndim - 1)), new, old),
                        lc, c2)
                    return y, c2

                body = jax.checkpoint(body_d) if cfg.remat else body_d
                if run == 1:
                    x, c1 = body(x, (jax.tree.map(lambda a: a[0], seg_params),
                                     jax.tree.map(lambda a: a[0], seg_cache)))
                    seg_cache_out = jax.tree.map(lambda a: a[None], c1)
                else:
                    x, seg_cache_out = jax.lax.scan(body, x,
                                                    (seg_params, seg_cache))
                new_caches[kind].append(seg_cache_out)
            elif prefix_caches is not None:
                # chunked prefill: thread this segment's seeded cache rows
                # through the scan so each layer attends over its own prefix
                cache_off = _cache_offset(new_caches[kind])
                seg_prefix = jax.tree.map(
                    lambda a: jax.lax.slice_in_dim(a, cache_off,
                                                   cache_off + run, axis=0),
                    prefix_caches[kind])

                def body_pc(carry, xs):
                    xc, aux = carry
                    lp, lc = xs
                    y, c2, a = _apply_block_prefill(
                        cfg, kind, lp, xc, positions, impl,
                        segment_ids, prefix=lc, prefix_len=prefix_len,
                        prefix_positions=prefix_positions,
                        prefix_segment_ids=prefix_segment_ids)
                    return (y, aux + a), c2

                body = jax.checkpoint(body_pc) if cfg.remat else body_pc
                if run == 1:
                    (x, aux_total), c1 = body(
                        (x, aux_total),
                        (jax.tree.map(lambda a: a[0], seg_params),
                         jax.tree.map(lambda a: a[0], seg_prefix)))
                    seg_cache_out = jax.tree.map(lambda a: a[None], c1)
                else:
                    (x, aux_total), seg_cache_out = jax.lax.scan(
                        body, (x, aux_total), (seg_params, seg_prefix))
                new_caches[kind].append(seg_cache_out)
            else:
                def body_p(carry, lp):
                    xc, aux = carry
                    y, c2, a = _apply_block_prefill(cfg, kind, lp, xc,
                                                    positions, impl,
                                                    segment_ids)
                    return (y, aux + a), c2

                body = jax.checkpoint(body_p) if cfg.remat else body_p
                if run == 1:
                    (x, aux_total), c1 = body(
                        (x, aux_total),
                        jax.tree.map(lambda a: a[0], seg_params))
                    seg_cache_out = jax.tree.map(lambda a: a[None], c1)
                else:
                    (x, aux_total), seg_cache_out = jax.lax.scan(
                        body, (x, aux_total), seg_params)
                new_caches[kind].append(seg_cache_out)
            n_done += run
            sub_start += run
            if every and n_done % every == 0 and shared_i < num_shared_invocations(cfg):
                if decode:
                    x, caches["shared"] = _shared_attn_decode(
                        cfg, params, x, pos, active, caches["shared"],
                        shared_i, impl)
                else:
                    sprefix = None
                    if prefix_caches is not None:
                        sprefix = {
                            "k": prefix_caches["shared"]["k"][shared_i],
                            "v": prefix_caches["shared"]["v"][shared_i]}
                    x, (k, v) = _shared_attn_prefill(
                        cfg, params, x, positions, impl, segment_ids,
                        prefix=sprefix, prefix_len=prefix_len,
                        prefix_positions=prefix_positions,
                        prefix_segment_ids=prefix_segment_ids)
                    shared_caches.append((k, v))
                shared_i += 1

    out_caches: Cache = {}
    for kind, lst in new_caches.items():
        if lst:
            out_caches[kind] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *lst) \
                if len(lst) > 1 else lst[0]
    if decode:
        for kind in (ATTN, "shared"):
            if kind in caches:
                out_caches[kind] = caches[kind]
    elif shared_caches:
        out_caches["shared"] = {
            "k": jnp.stack([c[0] for c in shared_caches]),
            "v": jnp.stack([c[1] for c in shared_caches]),
        }
    return x, out_caches, aux_total


def _cache_offset(collected: List) -> int:
    off = 0
    for c in collected:
        leaf = jax.tree.leaves(c)[0]
        off += leaf.shape[0]
    return off


def forward_train(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  embeds: Optional[jax.Array] = None, impl: str = "xla"
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S_total, V), moe aux loss)."""
    x = embed_inputs(cfg, params, tokens, embeds)
    x = _constrain_acts(x)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, aux = _run_stack(cfg, params, x, positions, impl)
    return logits_fn(cfg, params, x), aux


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            embeds: Optional[jax.Array] = None, impl: str = "xla",
            last_only: bool = False,
            positions: Optional[jax.Array] = None,
            segment_ids: Optional[jax.Array] = None,
            prefix_caches: Optional[Cache] = None,
            prefix_len=None, prefix_positions=None,
            prefix_segment_ids=None) -> Tuple[jax.Array, Cache]:
    """Returns (logits, caches seeded with the prompt). ``last_only``
    projects only the final position — serving prefill never needs the
    (B, S, vocab) tensor.

    Token-packed prefill: pass ``segment_ids`` (B, S) plus ``positions``
    that restart at 0 per segment — several prompts concatenated along the
    sequence axis then attend block-diagonally with no batch padding. Only
    valid for pure-attention stacks (recurrent blocks would fold foreign
    segments into their state).

    Chunked prefill: pass ``prefix_caches`` (the request's seeded decode-
    cache rows, layer-stacked like ``init_cache`` output) plus
    ``prefix_len`` (scalar: valid prefix slots) and absolute ``positions``
    starting at the chunk offset — each attention layer attends over its
    seeded prefix and the chunk itself, and the returned caches hold the
    *chunk's* K/V only.

    Packed multi-request chunked prefill: additionally pass
    ``segment_ids`` (B, T) for the packed chunk wave and, instead of the
    scalar ``prefix_len``, per-prefix-slot ``prefix_positions`` /
    ``prefix_segment_ids`` (B, C) — the prefix axis concatenates every
    request's cache-prefix view; each chunk attends block-diagonally over
    its own view plus itself. Attention-pure stacks only.

    Recurrent chunked prefill (pure SSM/xLSTM stacks): ``prefix_caches``
    carries the per-layer recurrent-state snapshots from the previous
    chunk (the shape the prefill itself returns) — the chunk continues
    the recurrence instead of recomputing its prefix, O(n) total across
    chunks. Returned caches are the updated snapshots.
    """
    kinds = set(cfg.pattern())
    if segment_ids is not None:
        assert kinds <= {ATTN}, \
            "token-packed prefill requires a pure-attention stack"
        assert embeds is None, "packed prefill does not take extra embeds"
    if prefix_caches is not None:
        if kinds <= {ATTN}:
            assert positions is not None
            assert (prefix_len is not None) or (
                prefix_positions is not None
                and prefix_segment_ids is not None)
            assert segment_ids is None or prefix_positions is not None, \
                "a packed chunk wave needs per-slot prefix positions"
        else:
            # recurrent state resume: positions are meaningless to the
            # recurrence and attention layers have no snapshot to resume
            assert not (kinds & {ATTN}) and not num_shared_invocations(cfg), \
                "chunk resume needs a pure-attention (kv prefix) or " \
                "pure-recurrent (state snapshot) stack"
            assert segment_ids is None
    x = embed_inputs(cfg, params, tokens, embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, caches, _ = _run_stack(cfg, params, x, positions, impl,
                              segment_ids=segment_ids,
                              prefix_caches=prefix_caches,
                              prefix_len=prefix_len,
                              prefix_positions=prefix_positions,
                              prefix_segment_ids=prefix_segment_ids)
    if last_only:
        return logits_fn(cfg, params, x[:, -1]), caches
    return logits_fn(cfg, params, x), caches


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                pos: jax.Array, caches: Cache, impl: str = "xla"
                ) -> Tuple[jax.Array, Cache]:
    """tokens (B,1); pos (B,) absolute positions. Returns (logits (B,V), caches).

    A row with a negative ``pos`` is idle: its K/V rows and recurrent
    states are left as they are and its logits are meaningless. Each
    other row writes one K/V row per attention layer, in place in the
    (donated) stacked cache.
    """
    active = pos >= 0
    pos = jnp.maximum(pos, 0)
    x = jnp.take(params["embed/tok"], tokens, axis=0).astype(cfg.dtype)
    x, new_caches, _ = _run_stack(cfg, params, x, None, impl,
                                  decode=True, pos=pos, caches=caches,
                                  active=active)
    return logits_fn(cfg, params, x[:, 0]), new_caches


def seed_cache(cfg: ModelConfig, cache: Cache, prefill_caches: Cache,
               prompt_len: int) -> Cache:
    """Copy prefill outputs into a decode cache of larger capacity.

    Attention K/V from the prompt land at their absolute positions (ring-
    buffer slots for windowed attention); recurrent states are taken as-is.
    """
    out = dict(cache)

    def _place_kv(dst, src):
        # dst (L,B,C,K,hd), src (L,B,S,K,hd)
        C = dst.shape[2]
        S = src.shape[2]
        if S <= C:
            return jax.lax.dynamic_update_slice_in_dim(dst, src, 0, axis=2)
        # windowed: last C tokens, rotated so token p sits at slot p % C
        tail = src[:, :, S - C:]
        start = (S - C) % C
        rolled = jnp.roll(tail, shift=start, axis=2)
        return rolled

    for kind in (ATTN, "shared"):
        if kind in cache and kind in prefill_caches:
            out[kind] = {
                "k": _place_kv(cache[kind]["k"], prefill_caches[kind]["k"]),
                "v": _place_kv(cache[kind]["v"], prefill_caches[kind]["v"]),
            }
    for kind in (MAMBA, MLSTM, SLSTM):
        if kind in cache and kind in prefill_caches:
            out[kind] = prefill_caches[kind]
    return out


# --------------------------------------------------------------------------- #
# cache init
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Cache:
    """Decode caches at a given context capacity (window-clamped for attn)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    kc = kind_counts(cfg)
    hd = cfg.resolved_head_dim
    caches: Cache = {}
    if ATTN in kc:
        C = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
        shape = (kc[ATTN], batch, C, cfg.num_kv_heads, hd)
        caches[ATTN] = {"k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype)}
    if MAMBA in kc:
        one = ssm.ssm_init_cache(cfg, batch, dtype)
        caches[MAMBA] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (kc[MAMBA],) + a.shape).copy(), one)
    if MLSTM in kc:
        one = xlstm.mlstm_init_cache(cfg, batch)
        caches[MLSTM] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (kc[MLSTM],) + a.shape).copy(), one)
    if SLSTM in kc:
        one = xlstm.slstm_init_cache(cfg, batch)
        caches[SLSTM] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (kc[SLSTM],) + a.shape).copy(), one)
    n_inv = num_shared_invocations(cfg)
    if n_inv:
        kv = cfg.shared_attn_kv_heads or cfg.num_kv_heads
        shape = (n_inv, batch, capacity, kv, hd)
        caches["shared"] = {"k": jnp.zeros(shape, dtype),
                            "v": jnp.zeros(shape, dtype)}
    return caches
