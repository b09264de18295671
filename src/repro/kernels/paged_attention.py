"""Paged decode attention as a Pallas TPU kernel — the serving hot spot.

One query token per request attends to its paged KV cache. TPU adaptation
of vLLM's CUDA paged-attention: the grid walks (request, page-tile) with
page ids resolved from a scalar-prefetched block table. Every kv head of a
request is processed in the same grid step; GQA query heads of one kv head
are processed together as the tile's sublane dimension; flash-style
running max/sum scratch merges tiles.

Each grid step processes ``pages_per_step`` pages: the K/V pages live in
HBM (``memory_space=ANY``) and the kernel issues one manual async copy per
needed page into a double-buffered VMEM scratch tile, so

  * a step whose tile lies fully past ``context_lens[b]`` issues *no* DMA
    at all (the old BlockSpec pipeline prefetched every page of every
    request up to ``max_pages`` regardless of context length),
  * short contexts stop paying per-page grid-step overhead, and
  * tile ``s+1``'s copies are issued before tile ``s`` is consumed
    (revolving buffers), keeping the DMA/compute overlap the BlockSpec
    pipeline provided.

The per-page flash update loop is ordered exactly like the one-page-per-
step kernel, so results are bit-identical for any ``pages_per_step``.

The pages are viewed as (P, page * K, head_dim), row ``t * K + h`` holding
token t's kv head h. On the chip that view is the cache's own memory (TPU
tiles the last two dims by (8, 128), so with K % 8 == 0 a (K, head_dim)
token row is one tile either way) and is never copied; a page is one
contiguous DMA. A head's rows are read out of the VMEM tile with a strided
load; Mosaic strides 32-bit rows only, so a bfloat16 tile is read as
uint32 row pairs and the head's half kept. head_dim must be a multiple of
128 on the chip (interpret mode takes any shape).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_rows(tile, kh, *, n_heads: int, page: int, packed: bool):
    """Kv head ``kh``'s (page, hd) rows of a (page * n_heads, hd) tile, as
    float32."""
    if not packed:
        return tile[pl.ds(kh, page, stride=n_heads), :].astype(jnp.float32)
    # uint32 row j holds bfloat16 rows 2j (low half) and 2j + 1 (high half);
    # a bfloat16 is the top half of the float32 it widens to
    w = tile.bitcast(jnp.uint32)[pl.ds(kh // 2, page, stride=n_heads // 2), :]
    w = jnp.where(kh % 2 == 0, w << 16, w & jnp.uint32(0xFFFF0000))
    return jax.lax.bitcast_convert_type(w, jnp.float32)


def _kernel(block_tables, context_lens, q_ref, k_hbm, v_hbm, o_ref,
            m_scr, l_scr, acc_scr, k_tile, v_tile, sem, *,
            page: int, pages_per_step: int, scale: float,
            softcap: Optional[float], max_pages: int, n_steps: int,
            n_heads: int, packed: bool):
    b = pl.program_id(0)
    s = pl.program_id(1)
    ctx = context_lens[b]
    rows = functools.partial(_head_rows, n_heads=n_heads, page=page,
                             packed=packed)

    def tile_dma(t, buf, start):
        """Issue (or wait on) the copies for page tile ``t`` into revolving
        buffer ``buf``. Pages past the context or the table issue nothing —
        ``t == n_steps`` (the last step's prefetch) self-guards because its
        page indices are all >= max_pages."""
        for i in range(pages_per_step):
            pi = t * pages_per_step + i

            @pl.when((pi * page < ctx) & (pi < max_pages))
            def _(i=i, pi=pi):
                pid = block_tables[b, pi]
                ck = pltpu.make_async_copy(k_hbm.at[pid], k_tile.at[buf, i],
                                           sem.at[buf, 0, i])
                cv = pltpu.make_async_copy(v_hbm.at[pid], v_tile.at[buf, i],
                                           sem.at[buf, 1, i])
                if start:
                    ck.start()
                    cv.start()
                else:
                    ck.wait()
                    cv.wait()

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        tile_dma(0, 0, start=True)

    base = s * pages_per_step * page

    @pl.when(base < ctx)
    def _work():
        buf = jax.lax.rem(s, 2)
        tile_dma(s + 1, jax.lax.rem(s + 1, 2), start=True)   # prefetch
        tile_dma(s, buf, start=False)                        # arrive

        # flash updates page-by-page, each kv head in the exact op order of
        # the single-page kernel -> bit-identical output for any tile size
        for i in range(pages_per_step):
            pi = s * pages_per_step + i

            @pl.when((pi * page < ctx) & (pi < max_pages))
            def _step(i=i, pi=pi):
                def head(kh, carry):
                    q = q_ref[0, kh].astype(jnp.float32)      # (G, hd)
                    k = rows(k_tile.at[buf, i], kh)           # (page, hd)
                    v = rows(v_tile.at[buf, i], kh)
                    s_ = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    if softcap is not None:
                        s_ = softcap * jnp.tanh(s_ / softcap)  # (G, page)
                    tok = pi * page + jax.lax.broadcasted_iota(
                        jnp.int32, s_.shape, 1)
                    s_ = jnp.where(tok < ctx, s_, NEG_INF)

                    m_prev = m_scr[kh]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s_, axis=1, keepdims=True))
                    pexp = jnp.exp(s_ - m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    l_scr[kh] = alpha * l_scr[kh] + jnp.sum(
                        pexp, axis=1, keepdims=True)
                    acc_scr[kh] = acc_scr[kh] * alpha + jax.lax.dot_general(
                        pexp, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    m_scr[kh] = m_new
                    return carry

                jax.lax.fori_loop(0, n_heads, head, 0)

    @pl.when(s == n_steps - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           context_lens: jax.Array, *,
                           softcap: Optional[float] = None,
                           pages_per_step: int = 8,
                           interpret: bool = False) -> jax.Array:
    """q (B,H,hd); k/v_pages (P,page,K,hd); block_tables (B,MP) int32;
    context_lens (B,) int32. Returns (B,H,hd)."""
    B, H, hd = q.shape
    P, page, K, _ = k_pages.shape
    G = H // K
    MP = block_tables.shape[1]
    # a page holds every kv head: keep the two K and V buffers in 8 MiB
    page_bytes = page * K * hd * k_pages.dtype.itemsize
    pps = max(1, min(pages_per_step, MP, (2 << 20) // page_bytes))
    n_steps = -(-MP // pps)
    qg = q.reshape(B, K, G, hd)
    scale = 1.0 / (hd ** 0.5)

    packed = k_pages.dtype == jnp.bfloat16 and K % 2 == 0
    kernel = functools.partial(_kernel, page=page, pages_per_step=pps,
                               scale=scale, softcap=softcap,
                               max_pages=MP, n_steps=n_steps, n_heads=K,
                               packed=packed)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, s, bt, cl: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, s, bt, cl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
            pltpu.VMEM((2, pps, page * K, hd), k_pages.dtype),  # 2 buffers
            pltpu.VMEM((2, pps, page * K, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pps)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, qg, k_pages.reshape(P, page * K, hd),
      v_pages.reshape(P, page * K, hd))
    return out.reshape(B, H, hd)
