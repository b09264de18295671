"""Serving engine integration: continuous batching == isolated decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model
from repro.serving import GenRequest, SamplingParams, ServingEngine


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen3_8b").reduced().with_(dtype="float32",
                                                 param_dtype="float32")
    return ServingEngine(cfg, max_batch=4, capacity=128, rl_accuracy=1.0)


def _requests(cfg, n, seed=0, max_tokens=(3, 12)):
    rng = np.random.default_rng(seed)
    return [GenRequest(
        prompt=list(rng.integers(0, cfg.vocab_size, rng.integers(4, 20))),
        params=SamplingParams(
            max_new_tokens=int(rng.integers(*max_tokens))))
        for _ in range(n)]


def _ref_greedy(cfg, params, prompt, n):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = model.prefill(cfg, params, toks)
    cache = model.init_cache(cfg, 1, capacity=128, dtype=jnp.float32)
    cache = model.seed_cache(cfg, cache, caches, len(prompt))
    cur = int(jnp.argmax(logits[0, -1]))
    out = [cur]
    for i in range(n - 1):
        lg, cache = model.decode_step(
            cfg, params, jnp.asarray([[cur]], jnp.int32),
            jnp.asarray([len(prompt) + i], jnp.int32), cache)
        cur = int(jnp.argmax(lg[0]))
        out.append(cur)
    return out


def test_continuous_batching_matches_isolated_greedy(engine):
    cfg = engine.cfg
    reqs = _requests(cfg, 6)
    engine_out = engine.run(reqs)
    for g in engine_out:
        assert g.t_done is not None
        assert len(g.output) == g.params.max_new_tokens
        ref = _ref_greedy(cfg, engine.params, g.prompt,
                          g.params.max_new_tokens)
        assert ref == g.output


def test_eos_early_stop():
    cfg = get_config("musicgen_large").reduced().with_(
        dtype="float32", param_dtype="float32")
    eng = ServingEngine(cfg, max_batch=2, capacity=96, rl_accuracy=1.0)
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(0, cfg.vocab_size, 8))
    ref = _ref_greedy(cfg, eng.params, prompt, 16)
    # pick the second emitted token as "EOS" so it must stop at 2 tokens
    eos = ref[1]
    g = GenRequest(prompt=prompt,
                   params=SamplingParams(max_new_tokens=16, eos_token=eos))
    eng.run([g])
    assert g.output[-1] == eos
    assert len(g.output) < 16


def test_scheduler_stats_exposed(engine):
    # after the module-scoped runs the scheduler accounted everything
    s = engine.scheduler
    s.kvc.check_invariants()
    assert s.completed


def test_prefill_compile_count_bounded(engine):
    """Bucketed prefill: distinct traced shapes <= ceil(log2(max_prompt))
    (power-of-two sequence buckets at a fixed batch dimension)."""
    import math
    assert engine._pad_prefill
    max_ctx = max(len(g.prompt) + len(g.output)
                  for g in engine.requests.values())
    bound = max(1, math.ceil(math.log2(max(2, max_ctx))))
    assert engine.n_prefill_compiles <= bound
    assert len({b for b, _ in engine._prefill_shapes}) == 1  # one batch dim


def test_per_request_temperatures_not_collapsed():
    """Mixed greedy + hot-temperature batches: the greedy request must
    decode exactly its isolated greedy sequence (the old engine collapsed
    all temperatures to max(), breaking greedy requests)."""
    cfg = get_config("qwen3_8b").reduced().with_(dtype="float32",
                                                 param_dtype="float32")
    eng = ServingEngine(cfg, max_batch=4, capacity=128, rl_accuracy=1.0,
                        seed=3)
    rng = np.random.default_rng(5)
    greedy = GenRequest(
        prompt=list(rng.integers(0, cfg.vocab_size, 9)),
        params=SamplingParams(max_new_tokens=8, temperature=0.0))
    hot = [GenRequest(
        prompt=list(rng.integers(0, cfg.vocab_size, 7)),
        params=SamplingParams(max_new_tokens=8, temperature=1.5, top_k=3))
        for _ in range(2)]
    eng.run([greedy] + hot)
    want = _ref_greedy(cfg, eng.params, greedy.prompt, 8)
    assert greedy.output == want
    for g in hot:
        assert len(g.output) == 8


def test_recurrent_model_exact_prefill_fallback():
    """Models with recurrent blocks cannot take padded prefill (pad tokens
    would corrupt the state) — the engine must fall back and still serve."""
    cfg = get_config("xlstm_125m").reduced().with_(dtype="float32",
                                                   param_dtype="float32")
    eng = ServingEngine(cfg, max_batch=2, capacity=64, rl_accuracy=1.0)
    assert not eng._pad_prefill
    rng = np.random.default_rng(2)
    reqs = [GenRequest(prompt=list(rng.integers(0, cfg.vocab_size, 5 + i)),
                       params=SamplingParams(max_new_tokens=4))
            for i in range(2)]
    eng.run(reqs)
    for g, n in zip(reqs, (5, 6)):
        assert g.t_done is not None
        assert len(g.output) == 4
        assert g.output == _ref_greedy(cfg, eng.params, g.prompt, 4)


def test_packed_seed_ring_cache_matches_padded_path():
    """Sliding-window ring caches seeded from a token-packed wave keep each
    prompt's last C tokens at slot p % C: prompts longer than the window
    decode exactly as through the padded-batch seed path."""
    from repro.serving import EngineConfig
    cfg = get_config("qwen3_8b").reduced().with_(
        dtype="float32", param_dtype="float32", sliding_window=24)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, n))
               for n in (30, 9, 41, 26)]
    outs = []
    for packed in (True, False):
        eng = ServingEngine(cfg, max_batch=4, capacity=64, rl_accuracy=1.0,
                            engine_cfg=EngineConfig(packed_prefill=packed))
        assert eng._is_ring("A", eng.caches["A"])
        reqs = [GenRequest(prompt=p, params=SamplingParams(max_new_tokens=6))
                for p in prompts]
        eng.run(reqs)
        outs.append([g.output for g in reqs])
    assert outs[0] == outs[1]
    assert all(len(o) == 6 for o in outs[0])


@pytest.mark.parametrize("program", ["fused", "mega"])
def test_decode_program_makes_no_cache_sized_temp(program):
    """The decode iteration writes one K/V row per active slot in place in
    the donated cache: its scratch stays below the stacked cache's bytes.
    (A whole-cache select, or slicing every layer's slab out of the stack
    and writing it back, needs at least one more cache's worth.)"""
    cfg = get_config("qwen3_8b").reduced(layers=4, d_model=128).with_(
        dtype="float32", param_dtype="float32")
    eng = ServingEngine(cfg, max_batch=4, capacity=256, rl_accuracy=1.0)
    cache_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.caches))
    active = jnp.array([True, False, True, True])
    if program == "fused":
        lowered = eng._fused.lower(eng.params, eng.caches, eng._dev, active,
                                   False, False)
    else:
        lowered = eng._mega.lower(eng.params, eng.caches, eng._dev, active,
                                  jnp.int32(4), False, False, False)
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes, (temp, cache_bytes)
