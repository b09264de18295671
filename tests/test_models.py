"""Per-architecture smoke + prefill/decode equivalence on reduced configs.

Smoke (deliverable f): every assigned architecture instantiates a REDUCED
family variant (<=2 layers, d_model<=512, <=4 experts), runs one forward +
train step on CPU, asserts output shapes and no NaNs.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_configs, get_config, list_archs
from repro.models import model
from repro.training.optimizer import AdamWConfig, apply_updates, init_state
from repro.training.train_loop import make_train_step

ARCHS = list_archs(include_paper_model=True)


def _reduced(name, **kw):
    cfg = get_config(name).reduced().with_(dtype="float32",
                                           param_dtype="float32", **kw)
    if cfg.is_moe:
        cfg = cfg.with_(capacity_factor=float(cfg.num_experts))
    return cfg


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_forward_and_train_step(name):
    cfg = _reduced(name)
    assert cfg.num_layers <= 3 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    params = model.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend:
        batch["embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.frontend_tokens, cfg.d_model))
    logits, aux = model.forward_train(cfg, params, batch["tokens"],
                                      batch.get("embeds"))
    F = cfg.frontend_tokens if cfg.frontend else 0
    assert logits.shape == (B, S + F, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
    # one full train step
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    opt_state = init_state(params, AdamWConfig())
    params2, _, metrics = step(params, opt_state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert not jnp.isnan(params2["final_norm"]).any()


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_decode_matches_full_forward(name):
    cfg = _reduced(name)
    params = model.init(cfg, jax.random.PRNGKey(0))
    B, S, T = 2, 35, 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + T), 0,
                                cfg.vocab_size)
    embeds = None
    F = 0
    if cfg.frontend:
        F = cfg.frontend_tokens
        embeds = 0.02 * jax.random.normal(jax.random.PRNGKey(2),
                                          (B, F, cfg.d_model))
    full, _ = model.forward_train(cfg, params, tokens, embeds)
    pf, caches = model.prefill(cfg, params, tokens[:, :S], embeds)
    assert jnp.max(jnp.abs(pf - full[:, :F + S])) < 2e-3
    cache = model.init_cache(cfg, B, capacity=F + S + T, dtype=jnp.float32)
    cache = model.seed_cache(cfg, cache, caches, F + S)
    for t in range(T):
        pos = jnp.full((B,), F + S + t, jnp.int32)
        lg, cache = model.decode_step(cfg, params,
                                      tokens[:, S + t:S + t + 1], pos, cache)
        assert jnp.max(jnp.abs(lg - full[:, F + S + t])) < 2e-3


def test_sliding_window_ring_buffer_decode():
    cfg = _reduced("mistral_nemo_12b", sliding_window=16)
    params = model.init(cfg, jax.random.PRNGKey(1))
    B, S, T = 2, 37, 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S + T), 0,
                                cfg.vocab_size)
    full, _ = model.forward_train(cfg, params, tokens)
    _, caches = model.prefill(cfg, params, tokens[:, :S])
    cache = model.init_cache(cfg, B, capacity=S + T, dtype=jnp.float32)
    assert cache["A"]["k"].shape[2] == 16      # window-clamped
    cache = model.seed_cache(cfg, cache, caches, S)
    for t in range(T):
        pos = jnp.full((B,), S + t, jnp.int32)
        lg, cache = model.decode_step(cfg, params,
                                      tokens[:, S + t:S + t + 1], pos, cache)
        assert jnp.max(jnp.abs(lg - full[:, S + t])) < 2e-3


def _naive_decode(cfg, params, tokens, pos, caches, active):
    """Reference decode, one layer at a time: every row's new K/V is set at
    its slot (``.at[b, slot].set``), the row attends over the written
    layer, and afterwards inactive rows get their old caches back
    (``jnp.where(active, new, old)``)."""
    from repro.models import attention, mlp, ssm, xlstm
    from repro.models.common import rms_norm
    from repro.models.config import ATTN, MAMBA, MLSTM
    B = tokens.shape[0]
    rows = jnp.arange(B)
    new = {kind: dict(c) for kind, c in caches.items()}

    def attend(p, kind, i, x, nkv):
        C = new[kind]["k"].shape[2]
        q, k, v = attention._project_qkv(p, cfg, x, pos[:, None], nkv)
        windowed = cfg.sliding_window is not None and C == cfg.sliding_window
        slot = pos % C if windowed else jnp.minimum(pos, C - 1)
        ck = new[kind]["k"][i].at[rows, slot].set(k[:, 0])
        cv = new[kind]["v"][i].at[rows, slot].set(v[:, 0])
        new[kind] = {"k": new[kind]["k"].at[i].set(ck),
                     "v": new[kind]["v"].at[i].set(cv)}
        idx = jnp.arange(C)[None]
        mask = (jnp.mod(slot[:, None] - idx, C) <= pos[:, None] if windowed
                else idx <= slot[:, None])
        out = attention._sdpa(q, ck, cv, mask[:, None, :], cfg)
        return jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, -1), p["wo"])

    def sub(d, prefix):
        return {k[len(prefix):]: v for k, v in d.items()
                if k.startswith(prefix)}

    x = jnp.take(params["embed/tok"], tokens, axis=0).astype(cfg.dtype)
    seen = {}
    every = cfg.shared_attention_every
    for n, kind in enumerate(cfg.pattern(), 1):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        p = {k: v[i] for k, v in sub(params, kind + "/").items()}
        if kind == ATTN:
            x = x + attend(sub(p, "attn/"), ATTN, i,
                           rms_norm(x, p["norm1"], cfg.rms_eps),
                           cfg.num_kv_heads)
            x = x + mlp.mlp_apply(sub(p, "mlp/"),
                                  rms_norm(x, p["norm2"], cfg.rms_eps))
        else:
            h = rms_norm(x, p["norm"], cfg.rms_eps)
            state = {k: v[i] for k, v in new[kind].items()}
            step = {MAMBA: lambda: ssm.ssm_decode(sub(p, "ssm/"), cfg, h,
                                                  state),
                    MLSTM: lambda: xlstm.mlstm_decode(sub(p, "cell/"), cfg,
                                                      h, state)}[kind]
            y, state = step()
            new[kind] = {k: new[kind][k].at[i].set(v)
                         for k, v in state.items()}
            x = x + y
        if every and n % every == 0:
            sp = sub(params, "shared/")
            nkv = cfg.shared_attn_kv_heads or cfg.num_kv_heads
            x = x + attend(sub(sp, "attn/"), "shared", n // every - 1,
                           rms_norm(x, sp["norm1"], cfg.rms_eps), nkv)
            x = x + mlp.mlp_apply(sub(sp, "mlp/"),
                                  rms_norm(x, sp["norm2"], cfg.rms_eps))
    logits = model.logits_fn(cfg, params, x[:, 0])

    def sel(old, n):
        return jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)), n,
                         old)
    return logits, jax.tree.map(sel, caches, new)


DECODE_CFGS = {
    "qwen3_qk_norm": lambda: _reduced("qwen3_8b", num_layers=3),
    "nemo_q_width": lambda: _reduced("mistral_nemo_12b", head_dim=96),
    "sliding_window_ring": lambda: _reduced("mistral_nemo_12b",
                                            sliding_window=16),
    "zamba2_shared_attn": lambda: _reduced("zamba2_7b"),
    "hybrid_split_attn": lambda: _reduced(
        "zamba2_7b", num_layers=4, layer_pattern="AMMA"),
}


@pytest.mark.parametrize("case", ["inactive_rows", "pos_past_capacity"])
@pytest.mark.parametrize("name", sorted(DECODE_CFGS))
def test_decode_writes_only_active_rows(name, case):
    """decode_step with inactive rows, marked by a negative position as the
    engine marks them, against the naive reference: logits of active rows
    and every cache bitwise equal, inactive rows' caches and recurrent
    states untouched."""
    cfg = DECODE_CFGS[name]()
    params = model.init(cfg, jax.random.PRNGKey(3))
    B, cap = 4, 24
    caches = _random_caches(cfg, B, cap)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0,
                                cfg.vocab_size)
    active = jnp.array([True, False, True, False])
    if case == "inactive_rows":
        pos = jnp.array([5, 9, 17, 2], jnp.int32)
    else:       # past the capacity: the unwindowed slot clamps to C - 1
        pos = jnp.array([cap + 7, cap - 1, cap, cap + 30], jnp.int32)
    ref_logits, ref = jax.jit(_naive_decode, static_argnums=0)(
        cfg, params, tokens, pos, caches, active)
    logits, out = jax.jit(model.decode_step, static_argnums=0)(
        cfg, params, tokens, jnp.where(active, pos, -1), caches)
    assert (logits[active] == ref_logits[active]).all()
    for kind, c in out.items():
        for k in c:
            assert (c[k] == ref[kind][k]).all(), (kind, k)
            assert (c[k][:, ~active] == caches[kind][k][:, ~active]).all()


def _random_caches(cfg, B, cap):
    caches = model.init_cache(cfg, B, capacity=cap, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


@pytest.mark.parametrize("name", ["qwen3_qk_norm", "sliding_window_ring",
                                  "zamba2_shared_attn"])
def test_pallas_decode_reads_its_layer_of_the_stack(name):
    """impl="pallas" hands the kernel the whole stacked cache and picks the
    layer by block table: logits and written rows as the xla path's, up to
    the kernel's softmax order (a later layer's K/V inherits its rounding);
    the idle row untouched."""
    cfg = DECODE_CFGS[name]()
    params = model.init(cfg, jax.random.PRNGKey(3))
    B, cap = 4, 32
    caches = _random_caches(cfg, B, cap)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0,
                                cfg.vocab_size)
    pos = jnp.array([5, -1, 17, 40], jnp.int32)
    step = jax.jit(model.decode_step, static_argnums=(0, 5))
    want_logits, want = step(cfg, params, tokens, pos, caches, "xla")
    logits, out = step(cfg, params, tokens, pos, caches, "pallas")
    live = pos >= 0
    assert jnp.max(jnp.abs(logits[live] - want_logits[live])) < 1e-4
    for kind, c in out.items():
        for k in c:
            assert jnp.max(jnp.abs(c[k] - want[kind][k])) < 1e-4, (kind, k)
            assert (c[k][:, ~live] == caches[kind][k][:, ~live]).all()


def test_adamw_reduces_loss_direction():
    cfg = _reduced("xlstm_125m")
    params = model.init(cfg, jax.random.PRNGKey(0))
    opt = AdamWConfig(lr=1e-2, warmup_steps=1)
    state = init_state(params, opt)
    g = jax.tree.map(jnp.ones_like, params)
    p2, state2, gnorm = apply_updates(params, g, state, opt)
    assert float(gnorm) > 0
    assert int(state2["step"]) == 1
    # params moved against the gradient
    assert float(p2["final_norm"][0]) < float(params["final_norm"][0])
