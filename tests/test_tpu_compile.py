"""Compile the Pallas kernels for a described TPU v5e chip (no chip needed).

Interpret mode accepts block shapes and DMA slices that Mosaic refuses, so
the kernel tests alone cannot show that the kernels build for the chip.
These cases lower each kernel with ``interpret=False`` at Qwen3-8B's head
shapes (32 query / 8 kv heads, head_dim 128, bf16) and the serving
engine's sizes (2048-token prefill waves, 16 slots x 2048 cache rows), and
compile them with the TPU compiler against a ``v5e:2x2`` topology
description. Nothing runs; a refused block layout or DMA fails here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill import flash_attention
from repro.kernels.paged_attention import paged_decode_attention

H, K, HD = 32, 8, 128            # Qwen3-8B query heads, kv heads, head_dim
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _packed(T=2048):
    """Token-packed whole-prompt wave: one (1, T) row, segment mask."""
    def fn(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg, block_q=128,
                               block_k=128)
    return fn, [((1, T, H, HD), BF16), ((1, T, K, HD), BF16),
                ((1, T, K, HD), BF16), ((1, T), I32)]


def _chunk(T=512, n=2, Cp=1024):
    """Packed chunk wave: each chunk attends over its own cache-prefix
    view (explicit positions, key-side segment ids, Sq != Sk)."""
    Sk = n * Cp + T

    def fn(q, k, v, seg, kseg, qpos, kpos):
        return flash_attention(q, k, v, segment_ids=seg, kv_segment_ids=kseg,
                               q_positions=qpos, kv_positions=kpos,
                               block_q=128, block_k=128)
    return fn, [((1, T, H, HD), BF16), ((1, Sk, K, HD), BF16),
                ((1, Sk, K, HD), BF16), ((1, T), I32), ((1, Sk), I32),
                ((1, T), I32), ((1, Sk), I32)]


def _batched(B=4, S=512):
    """B > 1 rows with explicit positions (per-row int32 blocks)."""
    def fn(q, k, v, qpos, kpos):
        return flash_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                               block_q=128, block_k=128)
    return fn, [((B, S, H, HD), BF16), ((B, S, K, HD), BF16),
                ((B, S, K, HD), BF16), ((B, S), I32), ((B, S), I32)]


def _paged(B=16, C=2048, page=128):
    """Decode over the engine's contiguous cache rows viewed as pages."""
    P = B * C // page

    def fn(q, kp, vp, bt, cl):
        return paged_decode_attention(q, kp, vp, bt, cl)
    return fn, [((B, H, HD), BF16), ((P, page, K, HD), BF16),
                ((P, page, K, HD), BF16), ((B, C // page), I32), ((B,), I32)]


CASES = {"flash_packed": _packed, "flash_chunk_positions": _chunk,
         "flash_batched_positions": _batched, "paged_decode": _paged}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_step_copies_no_cache_slab_for_v5e(impl, one_chip,
                                                  no_persistent_cache):
    """The decode step at Qwen3-8B's widths over the engine's 16 x 2048
    cache, two layers: the new K/V rows are written in place and each
    layer is read where it lies, so the scratch stays below one layer's
    K and V slabs (a slab sliced out, or viewed in another layout for the
    kernel, would need that much at least)."""
    from repro.configs import get_config
    from repro.models import model
    cfg = get_config("qwen3_8b").with_(num_layers=2)
    B, C = 16, 2048

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, model.abstract(cfg))
    caches = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_cache(cfg, B, C)))
    tokens = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)

    def step(p, tok, pos, caches):
        return model.decode_step(cfg, p, tok, pos, caches, impl=impl)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, pos, caches).compile()
    slab = B * C * K * HD * 2 * 2                 # one layer's K and V, bf16
    assert compiled.memory_analysis().temp_size_in_bytes < slab
