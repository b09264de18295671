"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_attention
from repro.kernels.paged_attention import paged_decode_attention

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,win,cap", [
    (2, 256, 4, 2, 64, None, None),
    (1, 200, 8, 8, 128, None, None),       # MHA + ragged S (padding path)
    (2, 384, 4, 1, 64, 128, None),          # MQA + sliding window
    (1, 256, 2, 2, 64, None, 30.0),         # logit softcap
    (1, 130, 6, 3, 32, 64, None),           # odd everything
])
def test_flash_prefill_matches_ref(B, S, H, K, hd, win, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=True, window=win, softcap=cap,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=win, softcap=cap)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seg_lens,win,cap", [
    ((48, 80),       None, None),
    ((17, 60, 51),   None, 30.0),            # ragged segments + softcap
    ((100, 28),      32,   None),            # sliding window within segments
    ((5, 3, 90, 30), None, None),            # tiny segments
])
def test_flash_prefill_segment_mask(seg_lens, win, cap, dtype):
    """Token-packed (block-diagonal) masking: a flattened batch of segments
    must match per-segment exact-shape attention."""
    B, H, K, hd = 1, 4, 2, 32
    S = sum(seg_lens)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    seg = jnp.asarray(np.repeat(np.arange(len(seg_lens)), seg_lens)[None])
    out = flash_attention(q, k, v, causal=True, window=win, softcap=cap,
                          segment_ids=seg, block_q=32, block_k=32,
                          interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=win,
                               softcap=cap, segment_ids=seg)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])
    # the oracle itself equals isolated per-segment attention
    st = 0
    for L in seg_lens:
        alone = ref.flash_attention(q[:, st:st + L], k[:, st:st + L],
                                    v[:, st:st + L], causal=True,
                                    window=win, softcap=cap)
        np.testing.assert_allclose(
            want[:, st:st + L].astype(jnp.float32),
            alone.astype(jnp.float32), atol=TOLS[dtype], rtol=TOLS[dtype])
        st += L


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("C,S,plen,win,cap", [
    (64, 48, 40, None, None),               # prefix + chunk, padded C view
    (96, 17, 60, None, 30.0),               # ragged chunk + softcap
    (128, 33, 100, 48, None),               # sliding window across prefix
    (64, 48, 0, None, None),                # empty prefix (first chunk)
])
def test_flash_prefill_prefix_positions(C, S, plen, win, cap, dtype):
    """Chunked-prefill masking: explicit q/kv positions with a rectangular
    key axis (cache-prefix view of C slots, plen valid, then the chunk)
    must match (a) the positions-aware oracle and (b) the tail rows of a
    plain contiguous causal run over [prefix ++ chunk]."""
    from repro.kernels.flash_prefill import POS_INVALID
    B, H, K, hd = 2, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    vc = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    kp = jax.random.normal(ks[3], (B, C, K, hd), dtype)
    vp = jax.random.normal(ks[4], (B, C, K, hd), dtype)
    qpos = jnp.broadcast_to(plen + jnp.arange(S), (B, S))
    slot = jnp.arange(C)
    kpos = jnp.broadcast_to(jnp.concatenate(
        [jnp.where(slot < plen, slot, POS_INVALID),
         plen + jnp.arange(S)]), (B, C + S))
    k_all = jnp.concatenate([kp, kc], axis=1)
    v_all = jnp.concatenate([vp, vc], axis=1)
    out = flash_attention(q, k_all, v_all, causal=True, window=win,
                          softcap=cap, q_positions=qpos, kv_positions=kpos,
                          block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k_all, v_all, causal=True, window=win,
                               softcap=cap, q_positions=qpos,
                               kv_positions=kpos)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])
    # oracle cross-check: chunk-over-prefix == tail of the contiguous run
    kf = jnp.concatenate([kp[:, :plen], kc], axis=1)
    vf = jnp.concatenate([vp[:, :plen], vc], axis=1)
    qf = jnp.concatenate(
        [jax.random.normal(ks[1], (B, plen, H, hd), dtype), q], axis=1)
    full = ref.flash_attention(qf, kf, vf, causal=True, window=win,
                               softcap=cap)
    np.testing.assert_allclose(
        want.astype(jnp.float32), full[:, plen:].astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Cp,spans,win,cap", [
    (64, ((40, 24), (0, 30)),          None, None),   # mid + first chunk
    (64, ((60, 17), (32, 33), (5, 8)), None, 30.0),   # ragged 3-wave
    (96, ((90, 20), (48, 40)),         64,   None),   # window across prefix
])
def test_flash_prefill_packed_chunk_mask(Cp, spans, win, cap, dtype):
    """Packed multi-request chunked prefill: the key axis carries every
    segment's own prefix view (per-slot positions, POS_INVALID beyond
    each seeded prefix) plus the packed chunk wave, with separate q/kv
    segment arrays. The kernel must match the oracle, and the oracle must
    equal each request's isolated prefix-attending call."""
    from repro.kernels.flash_prefill import POS_INVALID
    B, H, K, hd = 1, 4, 2, 32
    n = len(spans)
    T = sum(L for _, L in spans)
    ks = jax.random.split(jax.random.PRNGKey(11), 3 + n)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, T, K, hd), dtype)
    vc = jax.random.normal(ks[2], (B, T, K, hd), dtype)
    prefixes = [jax.random.normal(ks[3 + i], (2, B, Cp, K, hd), dtype)
                for i in range(n)]
    qpos = np.zeros((B, T), np.int32)
    qseg = np.zeros((B, T), np.int32)
    ppos = np.zeros((B, n * Cp), np.int32)
    pseg = np.zeros((B, n * Cp), np.int32)
    off = 0
    for i, (start, L) in enumerate(spans):
        qpos[:, off:off + L] = start + np.arange(L)
        qseg[:, off:off + L] = i
        slot = np.arange(Cp)
        ppos[:, i * Cp:(i + 1) * Cp] = np.where(slot < start, slot,
                                                POS_INVALID)
        pseg[:, i * Cp:(i + 1) * Cp] = i
        off += L
    k_all = jnp.concatenate([p[0] for p in prefixes] + [kc], axis=1)
    v_all = jnp.concatenate([p[1] for p in prefixes] + [vc], axis=1)
    kpos = jnp.asarray(np.concatenate([ppos, qpos], axis=1))
    kseg = jnp.asarray(np.concatenate([pseg, qseg], axis=1))
    out = flash_attention(q, k_all, v_all, causal=True, window=win,
                          softcap=cap, segment_ids=jnp.asarray(qseg),
                          kv_segment_ids=kseg,
                          q_positions=jnp.asarray(qpos), kv_positions=kpos,
                          block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention(q, k_all, v_all, causal=True, window=win,
                               softcap=cap, segment_ids=jnp.asarray(qseg),
                               kv_segment_ids=kseg,
                               q_positions=jnp.asarray(qpos),
                               kv_positions=kpos)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])
    # oracle cross-check: each packed segment equals its isolated
    # single-request prefix-attending call
    off = 0
    for i, (start, L) in enumerate(spans):
        qi = q[:, off:off + L]
        ki = jnp.concatenate([prefixes[i][0], kc[:, off:off + L]], axis=1)
        vi = jnp.concatenate([prefixes[i][1], vc[:, off:off + L]], axis=1)
        slot = np.arange(Cp)
        kpos_i = jnp.asarray(np.concatenate(
            [np.where(slot < start, slot, POS_INVALID)[None].repeat(B, 0),
             qpos[:, off:off + L]], axis=1))
        alone = ref.flash_attention(
            qi, ki, vi, causal=True, window=win, softcap=cap,
            q_positions=jnp.asarray(qpos[:, off:off + L]),
            kv_positions=kpos_i)
        np.testing.assert_allclose(
            want[:, off:off + L].astype(jnp.float32),
            alone.astype(jnp.float32), atol=TOLS[dtype], rtol=TOLS[dtype])
        off += L


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,hd,page,MP", [
    (3, 8, 2, 64, 16, 5),
    (2, 4, 4, 128, 32, 4),
    (1, 8, 1, 64, 8, 7),                    # MQA
    (4, 2, 2, 32, 16, 3),                   # MHA tiny heads
])
def test_paged_decode_matches_ref(B, H, K, hd, page, MP, dtype):
    P = B * MP + 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (P, page, K, hd), dtype)
    vp = jax.random.normal(ks[2], (P, page, K, hd), dtype)
    rng = np.random.default_rng(0)
    bt = jnp.array(rng.permutation(P)[:B * MP].reshape(B, MP).astype(np.int32))
    cl = jnp.array(rng.integers(1, MP * page, B).astype(np.int32))
    out = paged_decode_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


def _paged_case(B, H, K, hd, page, MP, dtype=jnp.float32, seed=1):
    P = B * MP + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (P, page, K, hd), dtype)
    vp = jax.random.normal(ks[2], (P, page, K, hd), dtype)
    rng = np.random.default_rng(seed)
    bt = jnp.array(rng.permutation(P)[:B * MP].reshape(B, MP).astype(np.int32))
    return q, kp, vp, bt


@pytest.mark.parametrize("pps", [2, 3, 8])
def test_paged_decode_multipage_bit_identical_to_single_page(pps):
    """The pages_per_step tiling only batches DMA — the flash update order
    is unchanged, so outputs must be *bitwise* equal to one page per step."""
    B, H, K, hd, page, MP = 3, 8, 2, 64, 16, 5
    q, kp, vp, bt = _paged_case(B, H, K, hd, page, MP)
    cl = jnp.array([7, 40, MP * page], jnp.int32)
    one = paged_decode_attention(q, kp, vp, bt, cl, pages_per_step=1,
                                 interpret=True)
    many = paged_decode_attention(q, kp, vp, bt, cl, pages_per_step=pps,
                                  interpret=True)
    assert np.array_equal(np.asarray(one), np.asarray(many))


@pytest.mark.parametrize("softcap", [None, 25.0])
def test_paged_decode_gqa_softcap(softcap):
    """GQA (H > K) with softcap on/off, multi-page tile."""
    B, H, K, hd, page, MP = 2, 8, 2, 64, 16, 6
    q, kp, vp, bt = _paged_case(B, H, K, hd, page, MP, seed=4)
    cl = jnp.array([50, 90], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cl, softcap=softcap,
                                 pages_per_step=4, interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, bt, cl, softcap=softcap)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_paged_decode_context_shorter_than_one_page():
    """Contexts inside the first page: every later grid step must early-exit
    without touching its pages."""
    B, H, K, hd, page, MP = 3, 4, 4, 32, 32, 8
    q, kp, vp, bt = _paged_case(B, H, K, hd, page, MP, seed=5)
    cl = jnp.array([1, 5, 31], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cl, pages_per_step=4,
                                 interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_paged_decode_context_equals_capacity():
    """context == max_pages * page: the final (partial) tile is exercised."""
    B, H, K, hd, page, MP = 2, 4, 2, 64, 16, 5   # 5 pages, pps 2 -> tail 1
    q, kp, vp, bt = _paged_case(B, H, K, hd, page, MP, seed=6)
    cl = jnp.array([MP * page, MP * page], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cl, pages_per_step=2,
                                 interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_contiguous_wrapper():
    from repro.kernels import ops
    B, C, K, hd, H = 2, 96, 2, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    ck = jax.random.normal(ks[1], (B, C, K, hd))
    cv = jax.random.normal(ks[2], (B, C, K, hd))
    ctx = jnp.array([40, 96], jnp.int32)
    out = ops.decode_attention(q, ck, cv, ctx)
    mp = C // 32
    bt = (jnp.arange(B)[:, None] * mp + jnp.arange(mp)[None, :]).astype(jnp.int32)
    want = ref.paged_decode_attention(q, ck.reshape(B * mp, 32, K, hd),
                                      cv.reshape(B * mp, 32, K, hd), bt, ctx)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_reads_one_layer_of_a_stack():
    """A stacked (L,B,C,K,hd) cache with a layer index reads exactly what
    the layer's own (B,C,K,hd) slab gives."""
    from repro.kernels import ops
    L, B, C, K, hd, H = 3, 2, 64, 2, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    ck = jax.random.normal(ks[1], (L, B, C, K, hd))
    cv = jax.random.normal(ks[2], (L, B, C, K, hd))
    ctx = jnp.array([17, 64], jnp.int32)
    for layer in range(L):
        out = ops.decode_attention(q, ck, cv, ctx, jnp.int32(layer))
        want = ops.decode_attention(q, ck[layer], cv[layer], ctx)
        assert (out == want).all(), layer


def test_kv_page_append_roundtrip():
    from repro.kernels.ref import kv_page_append
    B, page, K, hd, MP = 2, 8, 2, 16, 3
    P = B * MP
    kp = jnp.zeros((P, page, K, hd))
    vp = jnp.zeros((P, page, K, hd))
    bt = jnp.arange(P, dtype=jnp.int32).reshape(B, MP)
    k_new = jnp.ones((B, K, hd))
    pos = jnp.array([0, 13], jnp.int32)
    kp2, vp2 = kv_page_append(kp, vp, k_new, k_new * 2, bt, pos)
    assert float(kp2[bt[0, 0], 0].sum()) == K * hd
    assert float(kp2[bt[1, 1], 5].sum()) == K * hd
    assert float(vp2[bt[1, 1], 5].sum()) == 2 * K * hd
