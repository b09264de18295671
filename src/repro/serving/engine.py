"""Continuous-batching serving engine: real JAX model execution driven by
any `repro.core` scheduler (EconoServe by default).

The scheduler owns KVC block accounting, batching policy, SLO ordering,
and KVC pipelining; the engine owns slots, caches, jitted prefill/decode
steps and sampling. Completion is EOS- or max-tokens-driven; when EOS
fires early the request's `true_rl` is clamped so the scheduler sees the
real completion (the RL predictor only ever saw the prompt).

Hot-path layout (why the shapes look the way they do):

  * Decode is *fully asynchronous and device-resident* (default,
    ``EngineConfig.async_decode``): per-slot ``last_tok`` / ``pos`` /
    sampling params live as device arrays carried across iterations, and
    decode -> sample -> EOS-check -> pos-update run as ONE jitted,
    buffer-donated step (XLA reuses the cache buffers in place). Sampled
    tokens are drained to the host with a lag of
    ``EngineConfig.readback_lag`` iterations — the host appends tokens for
    iteration t-k while iteration t runs on device, so the steady-state
    loop issues zero blocking host syncs (``sync_counts`` /
    ``n_blocking_syncs`` instrument this). Only when an *active* request
    carries an ``eos_token`` does the engine read back a (B,) flag vector
    per iteration, because the scheduler's completion accounting needs EOS
    at the iteration it fires to stay bitwise-equal to the sync path.
  * Prefill is *token-packed* (default, ``EngineConfig.packed_prefill``):
    all PT items of an iteration are concatenated into one flattened token
    axis with per-segment positions and a block-diagonal segment mask —
    no batch-dim padding and no per-row length padding; the only padding
    left is rounding the total token count up to a pow2 bucket, so XLA
    compiles <= ceil(log2(max_total_tokens)) programs per engine lifetime.
    Models with recurrent blocks (SSM/xLSTM) fall back to exact-shape
    prefill, where foreign segments would corrupt the recurrent state; the
    legacy (max_batch, pow2-seq) padded-batch path is kept behind
    ``packed_prefill=False`` for the equivalence tests.
  * Decode *megasteps* (default, ``EngineConfig.decode_megastep`` > 1):
    when the scheduler proves a horizon of K iterations with fixed batch
    membership (``BaseScheduler.decode_horizon``), the engine runs
    K fused iterations as ONE dispatched ``lax.while_loop`` program and
    the host replays the K scheduler iterations against the precomputed
    (K, B) token matrix — decisions stay bitwise-identical to the
    per-iteration path while steady-state dispatch cost is amortized K×
    (``n_decode_dispatches`` / ``decode_iters`` instruments it). The
    horizon survives *memory pressure*: non-empty waiting queues are
    certified KVC-blocked from O(1) counters
    (``_admission_horizon``), so windows keep fusing exactly where the
    saturated steady state lives. EOS may fire inside a window: with
    empty queues completions only shrink the batch and the replay
    handles them; under pressure the freed KVC could admit a waiter, so
    the fused loop early-exits right after the EOS iteration
    (``stop_on_eos``) and admission lands at the exact iteration the
    K=1 path would admit.
  * Prefill is *chunk-capable*: the engine executes the scheduler's
    per-chunk PT grants (``_fill_pts``) instead of requiring TFS >= max
    prompt length. A chunk attends over the request's already-seeded
    cache prefix via a KV-prefix view threaded through ``model.prefill``
    → ``attn_prefill`` → the flash kernel and both jnp fallbacks, and its
    K/V seed the cache incrementally at [start, start+len). A wave of
    >= 2 chunk grants in one iteration runs as ONE token-packed call
    (default, ``EngineConfig.packed_chunk_prefill``): per-segment
    positions and segment ids over the packed chunks, each segment's
    own cache-prefix view prepended to the key axis, and one donated
    per-segment seed scatter. Pure-recurrent stacks (SSM/xLSTM) resume
    chunks from a carried per-request state snapshot (O(n) total);
    hybrid stacks fall back to recomputing the whole prefix each chunk
    (correct, O(n^2) across chunks); ``incremental_chunk_prefill=False``
    forces that reference path everywhere for the equivalence tests.
  * Cache seeding is one jitted, buffer-donated scatter over the whole
    item batch (a per-segment gather for the packed path) — not a
    per-layer host-side pytree rebuild.
  * Sampling is vectorized with per-slot temperature / top-k vectors and,
    on the async path, runs inside the decode program itself (no separate
    dispatch, no host round-trip).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import CostModel, ModelProfile
from repro.core.predictor import NoisyPredictor, apply_padding
from repro.core.pressure import WatermarkGuard
from repro.core.request import Request, State
from repro.core.scheduler import SchedulerConfig, make_econoserve
from repro.models import model
from repro.models.attention import POS_INVALID
from repro.models.config import ATTN, ModelConfig
from repro.obs.spans import span

from .sampling import SamplingParams, sample_in_graph, sample_per_request

MIN_SEQ_BUCKET = 16


class InvalidRequestError(ValueError):
    """Typed rejection for malformed ``GenRequest``s: the engine fails
    fast at ``submit`` instead of surfacing a deep scatter/shape error
    iterations later."""


class RequestShed(RuntimeError):
    """Typed admission rejection: the fleet's projected goodput says the
    request cannot meet its deadline, so it is fast-failed (marked
    ``status="shed"``) instead of queued into certain SLO violation.
    Carries the request as ``.request``."""

    def __init__(self, request, reason: str):
        super().__init__(reason)
        self.request = request
        self.reason = reason


class FleetStalled(RuntimeError):
    """``serve_stream`` watchdog: work remains but N consecutive steps
    made no progress (no completions, drains, dispatches, or deliveries).
    Carries a per-instance diagnostic snapshot as ``.debug``."""

    def __init__(self, msg: str, debug=None):
        super().__init__(msg)
        self.debug = debug or {}


def kv_checksum(kv: dict) -> int:
    """CRC over a KV-migration image, computed at export and verified at
    inject — a corrupted payload (fault injection, or a real transport
    bug) must degrade to the recompute fallback, never poison a cache."""
    import zlib
    crc = 0
    for kind in sorted(kv):
        for n in ("k", "v"):
            crc = zlib.crc32(np.ascontiguousarray(kv[kind][n]).tobytes(),
                             crc)
    return crc


def seq_bucket(n: int) -> int:
    """Power-of-two padded length (floor MIN_SEQ_BUCKET)."""
    b = MIN_SEQ_BUCKET
    while b < n:
        b <<= 1
    return b


@dataclass
class EngineConfig:
    """Engine hot-path toggles, mirroring the PR 1
    ``SchedulerConfig.incremental_queues`` convention: the fast paths are
    the default and ``False`` keeps the reference implementation for
    equivalence tests and benchmarks.

    ``readback_lag`` is how many decode iterations sampled tokens may trail
    on device before the host materializes them; ``max_pending`` is the
    hard cap on undrained *dispatches* (a K-iteration megastep window
    counts once; beyond it the host accepts one blocking sync rather than
    queueing unboundedly).

    ``decode_megastep`` is the max fused decode iterations per dispatch
    (1 = the per-iteration async path; requires ``async_decode``).
    ``incremental_chunk_prefill=False`` makes every prompt chunk recompute
    its full prefix instead of attending over the seeded cache view — the
    reference path the incremental one is equivalence-tested against
    (it also covers the recurrent state-carry chunk path).
    ``packed_chunk_prefill=False`` keeps the one-call-per-chunk reference
    path: by default a wave of >= 2 chunk grants in one iteration runs as
    ONE token-packed dispatch with per-segment prefix views.

    ``host_swap`` enables the host-offload KV swap tier (rung 2 of the
    pressure-degradation ladder): when a swapped/evicted GT loses its
    engine slot its live cache pages are captured to a bounded host pool
    and restored on next schedule instead of recomputed. It only replaces
    recompute with a bitwise-identical page restore, so it is on by
    default; ``host_pool_frac`` sizes the pool relative to device KVC.
    ``swap_watermarks`` additionally arms the proactive
    ``WatermarkGuard`` controller (EWMA'd occupancy, high/low hysteresis)
    that swaps waiting GTs out *before* allocation failures force
    reactive preemption, holding them out of admission until pressure
    releases — at most ``guard_max_swaps`` victims per trip observation.
    """
    async_decode: bool = True
    packed_prefill: bool = True
    readback_lag: int = 2
    max_pending: int = 8
    decode_megastep: int = 8
    incremental_chunk_prefill: bool = True
    packed_chunk_prefill: bool = True
    # --- tiered KVC degradation (host swap + watermark guard) ----------
    host_swap: bool = True
    host_pool_frac: float = 1.0
    swap_watermarks: bool = False
    guard_high: float = 0.92
    guard_low: float = 0.70
    guard_alpha: float = 0.5
    guard_patience: int = 2
    guard_max_swaps: int = 2


@dataclass
class GenRequest:
    prompt: List[int]
    params: SamplingParams = field(default_factory=SamplingParams)
    rid: int = -1
    output: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_done: Optional[float] = None
    # lifecycle stamps on the caller's clock (the ``now`` of submit/step):
    # the scheduler first sees the request (later than t_submit only when
    # an open megastep window held it), its first prompt grant, and the
    # step that sampled its first token; the last two are copied from the
    # scheduler's Request when the request completes
    t_admitted: Optional[float] = None
    t_scheduled: Optional[float] = None
    t_first_sampled: Optional[float] = None
    # --- fault tolerance / SLO enforcement -----------------------------
    deadline: float = float("inf")   # absolute (iteration-clock) deadline
    status: Optional[str] = None     # terminal: completed | aborted | shed
    fail_reason: Optional[str] = None

    @property
    def finished(self) -> bool:
        return self.status is not None or self.t_done is not None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Optional[dict] = None, *,
                 max_batch: int = 8, capacity: int = 512,
                 scheduler_cfg: Optional[SchedulerConfig] = None,
                 variant: str = "full", impl: str = "xla",
                 rl_accuracy: float = 0.8, seed: int = 0,
                 engine_cfg: Optional[EngineConfig] = None,
                 device: Optional[jax.Device] = None):
        """``device`` holds this engine's params (copied there when given
        from elsewhere), caches and carried slot state; every jitted step
        then runs on it. Defaults to the first device."""
        self.cfg = cfg
        self.impl = impl
        self.max_batch = max_batch
        self.capacity = capacity
        self.ecfg = engine_cfg or EngineConfig()
        self.device = device or jax.devices()[0]
        with jax.default_device(self.device):
            key = jax.random.PRNGKey(seed)
            self.params = self._place(params if params is not None
                                      else model.init(cfg, key))
            self.key = jax.random.PRNGKey(seed + 1)
            # slot-based caches
            self.caches = self._place(model.init_cache(cfg, max_batch,
                                                       capacity))

        scfg = scheduler_cfg or SchedulerConfig(
            kvc_tokens=max_batch * capacity, block_size=32,
            tfs=capacity, max_model_len=capacity,
            max_batch_reqs=max_batch)
        cost = CostModel(model=ModelProfile.from_config(cfg))
        self.scheduler = make_econoserve(scfg, cost, variant)
        self.predictor = NoisyPredictor(accuracy=rl_accuracy, seed=seed,
                                        bucket=scfg.bucket)

        self.slot_of: Dict[int, int] = {}
        self.free_slots = list(range(max_batch))
        # host mirrors of per-slot state. On the legacy sync path they are
        # authoritative; on the async path last_tok/pos are device-resident
        # (carried through the fused step) and the mirrors only hold
        # prefill-time values (temps/top_ks/eos drive the static sampling
        # flags without any device readback).
        self.pos = np.zeros(max_batch, np.int64)      # next absolute position
        self.last_tok = np.zeros(max_batch, np.int64)
        self.temps = np.zeros(max_batch, np.float32)  # per-slot sampling
        self.top_ks = np.zeros(max_batch, np.int32)
        self.requests: Dict[int, GenRequest] = {}
        self._rid = 0

        # right-padded / token-packed prefill is exact only for
        # pure-attention stacks (masking ignores pad positions and foreign
        # segments); recurrent blocks would fold them into their state, so
        # they get exact shapes
        self._pad_prefill = set(cfg.pattern()) <= {ATTN}
        self._async = self.ecfg.async_decode
        self._packed = self.ecfg.packed_prefill and self._pad_prefill
        self._prefill_shapes: Set[Tuple[int, int]] = set()
        # chunked prefill: incremental (prefix-view) execution needs an
        # attention-pure stack and non-ring caches (a ring prefix has no
        # identity-placement view); otherwise chunks recompute their prefix
        win = cfg.sliding_window
        self._chunk_incremental = (self.ecfg.incremental_chunk_prefill
                                   and self._pad_prefill
                                   and (win is None or capacity < win))
        # packed multi-request chunking: all of an iteration's chunk
        # grants flatten into one token-packed call with per-segment
        # prefix views (needs the incremental prefix path + packing)
        self._chunk_packed = (self.ecfg.packed_chunk_prefill
                              and self._chunk_incremental and self._packed)
        # pure-recurrent stacks (SSM/xLSTM, no attention or shared-attn
        # layers) chunk by carrying the per-request recurrent-state
        # snapshot across chunks — O(n) total instead of the O(n^2)
        # recompute fallback
        kinds = set(cfg.pattern())
        self._chunk_rec = (self.ecfg.incremental_chunk_prefill
                           and not (kinds & {ATTN})
                           and not model.num_shared_invocations(cfg))
        self._rec_state: Dict[int, dict] = {}       # rid -> state snapshot
        self._chunk_progress: Dict[int, int] = {}   # rid -> ctx tokens seeded
        self.n_prefill_chunks = 0
        self.n_chunk_calls = 0                      # chunk-prefill dispatches
        self.max_chunk_items_per_call = 0
        # decode megastep: K fused iterations per dispatch (async only)
        self._mega_max = max(1, int(self.ecfg.decode_megastep)) \
            if self.ecfg.async_decode else 1
        self._mega_toks: Optional[jax.Array] = None   # (Kmax, B) window
        self._mega_eos: Optional[np.ndarray] = None   # host (Kmax, B) flags
        self._mega_row = 0
        self._mega_left = 0
        # arrivals submitted while a window is open wait here (delivered
        # with their true arrival time once the window drains), as do KV
        # injections from a peer engine (cluster prefill→decode migration)
        self._arrivals: List[Tuple[Request, float]] = []
        self._pending_injects: List[Tuple[dict, float]] = []
        # aborts requested while a window is open are deferred the same
        # way (mutating batch membership mid-window would desync the
        # device state the window already computed against)
        self._pending_aborts: List[Tuple[int, float, str]] = []
        self.n_decode_dispatches = 0
        self.n_kv_exports = 0
        self.n_kv_injects = 0
        self.n_kv_rejects = 0        # corrupted KV images refused at inject
        self.n_aborted = 0
        self.n_shed = 0              # rung-4 terminal sheds (kvc-infeasible)
        self.n_prefill_waves = 0     # whole-prompt prefill dispatch waves

        # idempotent at-least-once delivery: every fleet-routed message
        # (submit / KV inject) carries a delivery key; a duplicated or
        # retransmitted copy of an already-accepted key is dropped here,
        # at the instance boundary, making delivery effectively
        # exactly-once. ``n_dup_completions`` counts second terminal
        # writers suppressed first-writer-wins — always zero unless the
        # dedup boundary leaked (audited by check_fleet_invariants).
        self._delivered: set = set()
        self.n_dup_deliveries = 0
        self.n_dup_completions = 0
        # fleet-level shed-retry tier: when the owning fleet enables
        # hand-back, rung-4 kvc-infeasible sheds are cancelled locally
        # (slot/KVC freed) but parked here non-terminal for the fleet to
        # re-route instead of being shed terminally
        self.fleet_shed_handback = False
        self.shed_handback: List[GenRequest] = []

        # host-offload KV swap tier (tiered KVC degradation, rung 2):
        # rid -> {"kv", "ctx", "crc"} page images captured when a
        # swapped/evicted GT loses its slot; restored by ``_swap_in``
        # instead of the rung-3 recompute re-prefill. Extents are
        # budgeted by the scheduler-side ``BlockKVC`` swap ledger.
        self._host_swap: Dict[int, dict] = {}
        kvc = self.scheduler.kvc
        kvc.host_pool_tokens = int(kvc.capacity_tokens
                                   * max(0.0, self.ecfg.host_pool_frac))
        self.guard = WatermarkGuard(
            high=self.ecfg.guard_high, low=self.ecfg.guard_low,
            alpha=self.ecfg.guard_alpha,
            patience=self.ecfg.guard_patience) \
            if self.ecfg.swap_watermarks else None
        self.n_swap_captures = 0     # page images offloaded to host
        self.n_swap_restores = 0     # restored via swap-in (no recompute)
        self.n_swap_rejects = 0      # corrupt host image -> recompute rung
        self.n_swap_drops = 0        # budget-refused capture -> recompute
        # chaos ``squeeze`` arriving inside an open megastep window is
        # deferred: eating free blocks mid-window could invalidate the
        # extension headroom the fused rows were certified against
        self._pending_squeeze = 0.0

        # async bookkeeping: device slot state carried across the fused
        # steps, plus the lag-N readback ring of (tokens, [(row, rid)]).
        # The PRNG key rides along so the steady-state loop does not even
        # dispatch a host-side split — the fused step splits in-graph,
        # consuming the exact same key stream as the sync path (prefill
        # swaps the carried leaf without materializing it).
        self._dev = self._place({
            "last_tok": np.zeros(max_batch, np.int32),
            "pos": np.zeros(max_batch, np.int32),
            "temps": np.zeros(max_batch, np.float32),
            "top_ks": np.zeros(max_batch, np.int32),
            "eos": np.full(max_batch, -1, np.int32),
            "key": self.key,
        })
        self._active_bytes: Optional[bytes] = None
        self._active_dev: Optional[jax.Array] = None
        # ring entries: (tokens, row, [(slot_row, rid)]). ``tokens`` is a
        # (B,) sampled batch (row None) or a (Kmax, B) megastep window
        # matrix shared by K entries, with ``row`` selecting the iteration.
        self._pending_drain: Deque[Tuple[jax.Array, Optional[int],
                                         List[Tuple[int, int]]]] = deque()
        # host-sync instrumentation (what the hot-path microbench reports).
        # Drain categories are classified deterministically at ENQUEUE
        # time from the dispatch sequence alone (PR 8 classified at drain
        # time via ``is_ready()``, which races with device timing and made
        # the per-category split machine-dependent; the total was stable):
        # eos_flags          — EOS-flag readbacks: one (B,) vector per
        #                      iteration, or one (K, B) matrix per megastep
        #                      window (only when an active request has an
        #                      eos_token)
        # drain_blocking     — pipeline-serializing materializations: the
        #                      legacy sync path's per-iteration sample
        #                      readback (the async ring never serializes —
        #                      this stays 0 on the async path)
        # drain_backpressure — ring entries enqueued while an *older
        #                      distinct dispatch* was still inside the lag
        #                      window: any wait their drain takes happens
        #                      with the device already fed
        # drain_ready        — ring entries whose whole lag window is
        #                      their own dispatch (or empty): lag-aged
        #                      copies by construction
        # flush              — forced full drains (completion/preemption/
        #                      idle)
        self.sync_counts = {"eos_flags": 0, "drain_blocking": 0,
                            "drain_backpressure": 0,
                            "drain_ready": 0, "flush": 0}
        # enqueue-time drain classification state: a monotone dispatch
        # sequence plus the last ``readback_lag`` enqueued sequence ids
        # (never buffer identities — Python id() reuse is allocator-timing
        # dependent)
        self._drain_seq = 0
        self._recent_drain_seqs: Deque[int] = deque(
            maxlen=max(1, self.ecfg.readback_lag))
        self.n_tokens_drained = 0    # tokens materialized through the ring
        self.decode_iters = 0
        # run-ahead: at each async decode dispatch, the decode iterations
        # already dispatched whose token rows are not drained yet (summed),
        # and the number of such dispatches
        self.ahead_iters_sum = 0
        self.n_ahead_samples = 0
        # metrics plane (repro.obs): an attached MetricsSampler is invoked
        # at the end of every step — host-side reads only, no device ops,
        # so metrics-on stays bitwise-identical with zero added syncs
        self.metrics = None

        def _decode_fn(p, tok, pos, caches, active):
            """Forward pass of the active slots. Inactive rows get a negative
            position, so the model leaves their K/V rows and recurrent
            states (SSM/xLSTM) as they are."""
            return model.decode_step(cfg, p, tok, jnp.where(active, pos, -1),
                                     caches, impl=impl)

        self._decode = jax.jit(_decode_fn, donate_argnums=(3,))

        def _one_iter(p, caches, st, active, need_sample, need_topk):
            """One fused async decode iteration: forward pass writing the
            active slots' K/V rows in place, in-graph RNG split + sampling,
            EOS check and pos advance in one traced body. Shared verbatim
            by the single-step program and the megastep while_loop so both
            produce bitwise-identical results."""
            logits, new_caches = _decode_fn(
                p, st["last_tok"][:, None], st["pos"], caches, active)
            temps = jnp.where(active, st["temps"], 0.0)
            top_ks = jnp.where(active, st["top_ks"], 0)
            key, sk = jax.random.split(st["key"])
            new = sample_in_graph(logits, sk, temps, top_ks,
                                  need_sample, need_topk)
            eos_hit = active & (st["eos"] >= 0) & (new == st["eos"])
            st = dict(st,
                      last_tok=jnp.where(active, new, st["last_tok"]),
                      pos=st["pos"] + active.astype(st["pos"].dtype),
                      key=key)
            return new_caches, st, new, eos_hit

        self._fused = jax.jit(_one_iter, static_argnums=(4, 5),
                              donate_argnums=(1, 2))

        Kmax = self._mega_max

        def _mega_fn(p, caches, st, active, k_iters, need_sample, need_topk,
                     stop_on_eos):
            """Decode megastep: run up to ``k_iters`` (dynamic, <= Kmax)
            fused iterations in ONE dispatched while_loop, collecting each
            iteration's sampled tokens and EOS flags into (Kmax, B)
            buffers the host replays the scheduler against. ``caches`` and
            ``st`` are donated exactly as in the single-step program.

            ``stop_on_eos`` (static): under memory pressure (non-empty
            queues certified KVC-blocked) an EOS completion frees KVC that
            the K=1 path would hand to a waiter at the very next
            iteration, so the loop exits after the iteration where EOS
            fired — the carried RNG key and caches then advanced exactly
            as many times as the per-iteration path, and the host resumes
            fresh scheduling there (it recovers the executed count from
            the EOS matrix; rows past the exit stay zero)."""
            def cond(c):
                return (c[0] < k_iters) & ~c[1]

            def body(c):
                i, stop, caches, st, tb, eb = c
                caches, st, new, eos_hit = _one_iter(
                    p, caches, st, active, need_sample, need_topk)
                if stop_on_eos:
                    stop = jnp.any(eos_hit)
                return (i + 1, stop, caches, st,
                        tb.at[i].set(new), eb.at[i].set(eos_hit))

            init = (jnp.int32(0), jnp.asarray(False), caches, st,
                    jnp.zeros((Kmax, max_batch), jnp.int32),
                    jnp.zeros((Kmax, max_batch), bool))
            _, _, caches, st, tb, eb = jax.lax.while_loop(cond, body, init)
            return caches, st, tb, eb

        self._mega = jax.jit(_mega_fn, static_argnums=(5, 6, 7),
                             donate_argnums=(1, 2))

        def _seed_slots_fn(st, slots, first, fallback, use_first, poss,
                           temps, top_ks, eos):
            """Scatter prefill results into the carried device slot state
            (async path) — the first sampled token stays on device; rows
            re-prefilled after a preemption restore their last generated
            token from the host-known ``fallback``."""
            last = jnp.where(use_first, first, fallback)
            return dict(
                st,
                last_tok=st["last_tok"].at[slots].set(last, mode="drop"),
                pos=st["pos"].at[slots].set(poss, mode="drop"),
                temps=st["temps"].at[slots].set(temps, mode="drop"),
                top_ks=st["top_ks"].at[slots].set(top_ks, mode="drop"),
                eos=st["eos"].at[slots].set(eos, mode="drop"))

        self._seed_slots = jax.jit(_seed_slots_fn, donate_argnums=(0,))

        def _prefill_fn(p, toks, lens):
            logits, caches = model.prefill(cfg, p, toks, impl=impl)
            last = logits[jnp.arange(toks.shape[0]), lens - 1]
            return last, caches

        self._prefill = jax.jit(_prefill_fn)

        def _prefill_packed_fn(p, toks, pos, seg, last_idx):
            """Token-packed prefill: toks/pos/seg (1, T) with per-segment
            positions and segment ids; last_idx (Bb,) flat indices of each
            prompt's final token (pad rows point at 0 and are dropped by
            the caller's slot scatter)."""
            logits, caches = model.prefill(cfg, p, toks, impl=impl,
                                           positions=pos, segment_ids=seg)
            return logits[0, last_idx], caches

        self._prefill_packed = jax.jit(_prefill_packed_fn)

        def _chunk_fn(p, caches, toks, pos, slot, start, length):
            """Incremental chunk prefill + in-place seed: the chunk's
            queries attend over the slot's already-seeded cache prefix
            (slots [0, start)), and the chunk's K/V land at absolute slots
            [start, start+length) of the same donated cache row. Returns
            (caches, last-real-token logits)."""
            prefix = {kind: {n: jax.lax.dynamic_index_in_dim(
                sub[n], slot, axis=1, keepdims=True) for n in ("k", "v")}
                for kind, sub in caches.items()}
            logits, pf = model.prefill(cfg, p, toks, impl=impl,
                                       positions=pos, prefix_caches=prefix,
                                       prefix_len=start)
            last = logits[0, length - 1]
            Sb = toks.shape[1]
            out = {}
            for kind, sub in caches.items():
                C = sub["k"].shape[2]
                # pad positions (>= length) index C: out of bounds, dropped
                di = jnp.where(jnp.arange(Sb) < length,
                               jnp.minimum(start + jnp.arange(Sb), C), C)
                out[kind] = {n: sub[n].at[:, slot, di].set(
                    pf[kind][n][:, 0].astype(sub[n].dtype), mode="drop")
                    for n in ("k", "v")}
            return out, last

        self._chunk_prefill = jax.jit(_chunk_fn, donate_argnums=(1,))

        def _chunks_packed_fn(p, caches, toks, pos, seg, ppos, pseg, slots,
                              last_idx, src_idx, dst_idx):
            """Packed multi-request chunk prefill + seed: all chunk grants
            of an iteration run as ONE token-packed (1, T) call whose key
            axis prepends every segment's own cache-prefix view (gathered
            from the donated caches and block-diagonally masked via
            ``pseg``/``ppos`` — POS_INVALID beyond each seeded prefix);
            each chunk's K/V then scatter into its slot's row at
            [start, start+len) in the same donated program. Returns
            (caches, per-segment last-real-token logits)."""
            n = slots.shape[0]
            Cp = ppos.shape[1] // n
            prefix = {}
            for kind, sub in caches.items():
                prefix[kind] = {}
                for nm in ("k", "v"):
                    rows = jnp.take(sub[nm], slots, axis=1)  # (L,n,C,K,hd)
                    rows = jax.lax.slice_in_dim(rows, 0, Cp, axis=2)
                    L, _, _, Kh, hd = rows.shape
                    prefix[kind][nm] = rows.reshape(L, 1, n * Cp, Kh, hd)
            logits, pf = model.prefill(cfg, p, toks, impl=impl,
                                       positions=pos, segment_ids=seg,
                                       prefix_caches=prefix,
                                       prefix_positions=ppos,
                                       prefix_segment_ids=pseg)
            last = logits[0, last_idx]
            out = {}
            for kind, sub in caches.items():
                out[kind] = {}
                for nm in ("k", "v"):
                    # (L, n, W, K, hd) spans gathered from the packed axis;
                    # dst positions past each chunk's length index C (drop)
                    rows = jnp.take(pf[kind][nm][:, 0], src_idx, axis=1)
                    out[kind][nm] = sub[nm].at[
                        :, slots[:, None], dst_idx].set(
                        rows.astype(sub[nm].dtype), mode="drop")
            return out, last

        self._chunks_packed = jax.jit(_chunks_packed_fn, donate_argnums=(1,))

        def _rec_chunk_fn(p, states, toks):
            """Recurrent (SSM/xLSTM) chunk prefill resuming from the
            carried per-request state snapshot — the chunk continues the
            recurrence instead of recomputing its prefix. Exact shapes
            (recurrent stacks are not pad-tolerant), donated states."""
            logits, out_states = model.prefill(cfg, p, toks, impl=impl,
                                               prefix_caches=states)
            return out_states, logits[0, toks.shape[1] - 1]

        self._rec_chunk = jax.jit(_rec_chunk_fn, donate_argnums=(1,))

        def _inject_fn(caches, kv, slot, length):
            """Seed a migrated request's KV image into one cache row in a
            single donated program (cluster prefill→decode handoff). kv
            leaves are (L, Sb, K, hd) with real data in [0, length); pad
            positions index C and are dropped."""
            out = {}
            for kind, sub in caches.items():
                C = sub["k"].shape[2]
                Sb = kv[kind]["k"].shape[1]
                di = jnp.where(jnp.arange(Sb) < length, jnp.arange(Sb), C)
                out[kind] = {n: sub[n].at[:, slot, di].set(
                    kv[kind][n].astype(sub[n].dtype), mode="drop")
                    for n in ("k", "v")}
            return out

        self._inject_seed = jax.jit(_inject_fn, donate_argnums=(0,))
        self._seed = jax.jit(self._seed_fn, donate_argnums=(0,))
        self._seed_packed = jax.jit(self._seed_packed_fn,
                                    donate_argnums=(0,))

    def _place(self, tree):
        """Commit a pytree to this engine's device (no copy when it is
        already there)."""
        return jax.device_put(tree, self.device)

    @property
    def n_prefill_compiles(self) -> int:
        """Distinct (batch, seq) prefill shapes traced so far."""
        return len(self._prefill_shapes)

    @property
    def n_blocking_syncs(self) -> int:
        """Host syncs that can leave the device idle (EOS-flag readbacks +
        pipeline-serializing token drains). Zero across a steady-state
        async decode window with no EOS-capable requests. Backpressure
        drains — waits taken while newer dispatches were already queued on
        the device — are counted separately (``drain_backpressure``): the
        device stays fed through them."""
        return (self.sync_counts["eos_flags"]
                + self.sync_counts["drain_blocking"])

    # ------------------------------------------------------------------ #
    def submit(self, req: GenRequest, now: float,
               dkey: Optional[tuple] = None) -> int:
        """Register a request. While a fused megastep window is open the
        scheduler must not see the arrival (its admission would change
        batch membership the device already computed past): the arrival is
        buffered — with its true arrival time, so ordering/SLO math is
        unaffected — and delivered when the window drains, at most
        ``decode_megastep - 1`` iterations later. This is the standard
        multi-step-scheduling trade (scheduling decisions every K steps).

        ``dkey`` is the fleet transport's delivery key: a duplicated
        copy of an already-accepted delivery is dropped here (returns
        -1) before it can touch any engine state."""
        if dkey is not None:
            if dkey in self._delivered:
                self.n_dup_deliveries += 1
                return -1
            self._delivered.add(dkey)
        self.validate(req)
        req.rid = self._rid
        self._rid += 1
        req.t_submit = now
        r = Request(rid=req.rid, prompt_len=len(req.prompt),
                    true_rl=req.params.max_new_tokens, arrival=now,
                    slo_deadline=req.deadline)
        r.predicted_rl = self.predictor.predict(r)
        r.padded_rl = apply_padding(r.predicted_rl,
                                    self.scheduler.cfg.pad_ratio,
                                    self.scheduler.cfg.bucket)
        self.requests[req.rid] = req
        if self._mega_left > 0:
            self._arrivals.append((r, now))
        else:
            self.scheduler.on_arrival(r, now)
            req.t_admitted = now
        return req.rid

    def validate(self, req: GenRequest) -> None:
        """Reject malformed requests with a typed error at the submit
        boundary — the engine's shape machinery assumes a non-empty
        prompt that fits its cache row and KVC, and a positive token
        budget; violating any of these used to surface as a deep
        scatter/shape failure mid-iteration."""
        if req.params.max_new_tokens <= 0:
            raise InvalidRequestError(
                f"max_new_tokens must be >= 1, got "
                f"{req.params.max_new_tokens}")
        if not req.prompt:
            raise InvalidRequestError("empty prompt")
        kvc_cap = self.scheduler.kvc.capacity_tokens
        if len(req.prompt) + 1 > min(self.capacity, kvc_cap):
            raise InvalidRequestError(
                f"prompt of {len(req.prompt)} tokens (+1 response token) "
                f"exceeds capacity (cache row {self.capacity} slots, "
                f"KVC {kvc_cap} tokens)")

    def has_work(self) -> bool:
        """Scheduler work plus arrivals/injections/aborts buffered behind
        an open window."""
        return (self.scheduler.has_work() or bool(self._arrivals)
                or bool(self._pending_injects)
                or bool(self._pending_aborts))

    # ------------------------------------------------------------------ #
    # abort / cancellation (deadline enforcement, crash recovery)
    # ------------------------------------------------------------------ #
    def abort(self, rid: int, now: float, reason: str = "aborted") -> bool:
        """Cancel an in-flight request: force-drain the token ring (lag-N
        entries for the victim must materialize, never drop), detach it
        from the scheduler (freeing KVC) and release its engine slot.

        While a fused megastep window is open the abort is *deferred* —
        mutating batch membership mid-window would desync the device
        state the window precomputed — and applied when the window
        drains, exactly like deferred arrivals/injects. If the request
        completes inside the remaining window rows, completion wins and
        the abort becomes a no-op (terminal state stays exactly-once).

        Returns True when the abort was applied or queued, False when the
        rid is unknown or already terminal."""
        g = self.requests.get(rid)
        if g is None or g.finished:
            return False
        if self._mega_left > 0:
            if not any(p[0] == rid for p in self._pending_aborts):
                self._pending_aborts.append((rid, now, reason))
            return True
        self._apply_abort(rid, now, reason)
        return True

    def _apply_abort(self, rid: int, now: float, reason: str) -> None:
        assert self._mega_left == 0, "abort applied inside an open window"
        g = self.requests.get(rid)
        if g is None or g.finished:
            return                    # completed while the abort waited
        if self._pending_drain:
            # materialize ring tokens first: g.output must be complete
            # before the request leaves the engine (satellite: lag-N ring
            # entries for aborted requests are never dropped)
            self.sync_counts["flush"] += 1
            self._drain_tokens(force=True)
        for k, (r, _) in enumerate(self._arrivals):
            if r.rid == rid:          # still buffered behind a window
                self._arrivals.pop(k)
                break
        else:
            self.scheduler.cancel(rid, now)
        slot = self.slot_of.pop(rid, None)
        if slot is not None:
            self.free_slots.append(slot)
        self._chunk_progress.pop(rid, None)
        self._rec_state.pop(rid, None)
        self._host_swap.pop(rid, None)   # ledger entry dropped by cancel()
        g.status = "aborted"
        g.fail_reason = reason
        self.n_aborted += 1

    # ------------------------------------------------------------------ #
    # KV migration (cluster disaggregated prefill/decode roles)
    # ------------------------------------------------------------------ #
    @property
    def can_migrate_kv(self) -> bool:
        """A portable KV image needs identity cache placement: an
        attention-pure stack (recurrent states are not positionally
        addressable the same way) and non-ring caches (a sliding-window
        ring's layout depends on this engine's capacity)."""
        win = self.cfg.sliding_window
        return self._pad_prefill and (win is None or self.capacity < win)

    def export_kv(self, rid: int) -> dict:
        """Extract a queued GT's KV pages + carried slot state so a peer
        engine can continue decoding it (prefill→decode disaggregation),
        and remove the request from this engine and its scheduler.

        The returned payload feeds ``inject_kv``. ``payload["kv"]`` is the
        per-cache-kind {k, v} image of the request's first ``ctx`` context
        slots, or None when this engine cannot produce a portable image
        (recurrent stack, ring caches, or a request that lost its slot to
        preemption) — the receiver then falls back to the swap-recompute
        path, exactly like a swap-preempted GT.

        Must not be called while a fused megastep window is open: freeing
        the exported request's KVC mid-window could admit a waiter the
        window's precomputed rows never saw (``submit``/``inject_kv``
        defer for the same reason; export must return synchronously, so
        it asserts instead). Fleet callers only export from prefill-role
        instances, which never decode and so never open windows."""
        assert self._mega_left == 0, \
            "export_kv during an open megastep window"
        sched = self.scheduler
        req = next(r for r in sched.gt_queue if r.rid == rid)
        if self._pending_drain:
            # the payload must carry every token generated so far (the
            # receiver's recompute fallback rebuilds context from g.output)
            self.sync_counts["flush"] += 1
            self._drain_tokens(force=True)
        g = self.requests.pop(rid)
        slot = self.slot_of.pop(rid, None)
        kv = crc = None
        if slot is not None:
            if self._async:
                ctx = int(jax.device_get(self._dev["pos"][slot]))
                last = int(jax.device_get(self._dev["last_tok"][slot]))
            else:
                ctx = int(self.pos[slot])
                last = int(self.last_tok[slot])
            if self.can_migrate_kv:
                kv = {kind: {n: np.asarray(sub[n][:, slot, :ctx])
                             for n in ("k", "v")}
                      for kind, sub in self.caches.items()}
                crc = kv_checksum(kv)
            self.free_slots.append(slot)
        else:
            ctx = req.prompt_len + req.generated - 1
            last = g.output[req.generated - 1]
            # a host-offloaded image survives the slot loss: ship it (with
            # its capture-time CRC — recomputing here would vouch for a
            # corrupted pool) instead of sentencing the receiver to the
            # recompute fallback
            img = self._host_swap.pop(rid, None)
            if (img is not None and self.can_migrate_kv
                    and img["ctx"] == ctx):
                kv, crc = img["kv"], img["crc"]
        sched.gt_queue.remove(req)
        sched.kvc.free(rid)
        sched.kvc.swap_release(rid)
        sched.swap_hold.pop(rid, None)
        self._chunk_progress.pop(rid, None)
        self._rec_state.pop(rid, None)
        self._host_swap.pop(rid, None)
        req.occupied_kvc = req.prompt_len + req.generated
        self.n_kv_exports += 1
        return {"gen": g, "req": req, "kv": kv, "ctx": ctx,
                "last_tok": last, "kv_crc": crc}

    def inject_kv(self, payload: dict, now: float) -> Optional[int]:
        """Receive a migrated request. With a KV image (and a free slot +
        KVC room) the request becomes a queued GT whose decode continues
        from the injected pages; otherwise it queues with its KV "in host
        memory" and the engine's existing swap-recompute path re-prefills
        prompt + generated on first schedule. Deferred while a fused
        megastep window is open (same contract as ``submit``); returns the
        assigned rid, or None when deferred — or when the payload is a
        duplicated delivery (its ``dkey`` was already accepted — dedup
        happens here, before deferral, so a dup'd inject cannot even be
        double-buffered behind a window)."""
        dkey = payload.get("dkey")
        if dkey is not None:
            if dkey in self._delivered:
                self.n_dup_deliveries += 1
                return None
            self._delivered.add(dkey)
        if self._mega_left > 0:
            self._pending_injects.append((payload, now))
            return None
        return self._apply_inject(payload, now)

    def _apply_inject(self, payload: dict, now: float) -> int:
        g: GenRequest = payload["gen"]
        req: Request = payload["req"]
        rid = self._rid
        self._rid += 1
        g.rid = rid
        req.rid = rid
        self.requests[rid] = g
        sched = self.scheduler
        tokens = req.prompt_len + req.generated
        kv = payload["kv"]
        ctx = payload["ctx"]
        if kv is not None:
            crc = payload.get("kv_crc")
            if crc is not None and kv_checksum(kv) != crc:
                # corrupted in transit: refuse the image and degrade to
                # the recompute fallback — the host-side token stream is
                # the ground truth, so the output stays bitwise-correct
                kv = None
                self.n_kv_rejects += 1
        if (kv is not None and self.can_migrate_kv and self.free_slots
                and ctx <= self.capacity and sched.kvc.can_allocate(tokens)):
            sched.kvc.allocate(rid, tokens)
            sched.kvc.set_used(rid, tokens)
            slot = self.free_slots.pop()
            self.slot_of[rid] = slot
            # pad the image to a pow2 token bucket (clamped to capacity)
            # so the donated seeding program compiles <= log2(capacity)
            # times, mirroring the chunk-prefill shape policy
            Sb = seq_bucket(ctx)
            if Sb > self.capacity:
                Sb = max(ctx, self.capacity)
            padded = {}
            for kind, sub in kv.items():
                L, _, K, hd = sub["k"].shape
                padded[kind] = {}
                for n in ("k", "v"):
                    buf = np.zeros((L, Sb, K, hd), sub[n].dtype)
                    buf[:, :ctx] = sub[n]
                    padded[kind][n] = buf
            self.caches = self._inject_seed(self.caches, padded,
                                            np.int32(slot), np.int32(ctx))
            self.temps[slot] = g.params.temperature
            self.top_ks[slot] = g.params.top_k
            self.pos[slot] = ctx
            last = payload["last_tok"]
            if self._async:
                eos = -1 if g.params.eos_token is None else g.params.eos_token
                one = np.asarray([last], np.int32)
                self._dev = self._seed_slots(
                    self._dev, np.asarray([slot], np.int32),
                    jnp.asarray(one), jnp.asarray(one),
                    np.zeros(1, bool), np.asarray([ctx], np.int32),
                    np.asarray([g.params.temperature], np.float32),
                    np.asarray([g.params.top_k], np.int32),
                    np.asarray([eos], np.int32))
            else:
                self.last_tok[slot] = last
        else:
            # swap-recompute fallback: the request queues holding no KVC,
            # its KV notionally in host memory; when scheduled it arrives
            # in plan.decode_reqs without a slot and the engine re-prefills
            # prompt + generated (the existing preemption path)
            req.prompt_done = req.prompt_len
        req.occupied_kvc = tokens
        req.set_state(State.QUEUED_GT, now)
        sched.enqueue_gt(req)
        self.n_kv_injects += 1
        return rid

    # ------------------------------------------------------------------ #
    # host-offload KV swap tier (pressure ladder rung 2)
    # ------------------------------------------------------------------ #
    def _core_req(self, rid: int):
        """The scheduler-side Request still queued under ``rid`` (None
        when completed/aborted). ``gt_queue`` is an O(1)-indexed
        ``OrderedQueue`` on the default config, a plain list otherwise."""
        q = self.scheduler.gt_queue
        get = getattr(q, "get", None)
        if get is not None:
            return get(rid)
        return next((r for r in q if r.rid == rid), None)

    def _swap_out(self, rid: int, slot: int) -> None:
        """Rung-2 capture: offload a de-slotted GT's live cache pages to
        the bounded host pool before the slot is recycled. A refused
        capture (image over budget, recurrent/ring stack, offload-free
        preemption) falls through to rung 3 — the request recomputes on
        next schedule, exactly the pre-swap behavior."""
        if not (self.ecfg.host_swap and self.can_migrate_kv):
            return
        req = self._core_req(rid)
        if (req is None or req.prompt_done != req.prompt_len
                or req.generated < 1):
            return                     # offload-free preempt or terminal
        # the newest sampled token's KV was never written to cache — it is
        # the pending decode input (same invariant as export_kv/recompute)
        ctx = req.prompt_len + req.generated - 1
        if ctx <= 0 or ctx > self.capacity:
            return
        evicted = self.scheduler.kvc.swap_register(rid, ctx)
        if evicted is None:
            self.n_swap_drops += 1     # budget refusal -> recompute rung
            return
        for old in evicted:            # ledger evictions degrade a rung
            self._host_swap.pop(old, None)
        # blocks until the slot's dispatched decode work has landed, so
        # the image holds exactly ctx tokens of KV — a sync only paid on
        # the preemption path, never in the no-swap steady state
        kv = {kind: {n: np.asarray(sub[n][:, slot, :ctx])
                     for n in ("k", "v")}
              for kind, sub in self.caches.items()}
        self._host_swap[rid] = {"kv": kv, "ctx": ctx,
                                "crc": kv_checksum(kv)}
        self.n_swap_captures += 1

    def _swap_in(self, missing: List[Request], now: float) -> List[Request]:
        """Rung-2 restore: re-seed scheduled GTs whose KV pages are in the
        host pool, instead of the rung-3 recompute re-prefill. A corrupt
        or missing image degrades one rung (the request stays in
        ``missing`` and recomputes); a good image seeds exactly like a
        cluster KV inject, so greedy token streams stay bitwise-equal to
        the pressure-free run. Returns the requests left to recompute."""
        sched = self.scheduler
        left = []
        for r in missing:
            img = self._host_swap.pop(r.rid, None)
            if img is None:
                sched.kvc.swap_release(r.rid)   # evicted image, if any
                left.append(r)
                continue
            ctx = img["ctx"]
            ok = (self.can_migrate_kv and bool(self.free_slots)
                  and 0 < ctx <= self.capacity and r.generated >= 1
                  and kv_checksum(img["kv"]) == img["crc"])
            sched.kvc.swap_release(r.rid, restored=ok)
            if not ok:
                self.n_swap_rejects += 1        # corrupt image -> rung 3
                left.append(r)
                continue
            g = self.requests[r.rid]
            slot = self.free_slots.pop()
            self.slot_of[r.rid] = slot
            Sb = seq_bucket(ctx)
            if Sb > self.capacity:
                Sb = max(ctx, self.capacity)
            padded = {}
            for kind, sub in img["kv"].items():
                L, _, K, hd = sub["k"].shape
                padded[kind] = {}
                for n in ("k", "v"):
                    buf = np.zeros((L, Sb, K, hd), sub[n].dtype)
                    buf[:, :ctx] = sub[n]
                    padded[kind][n] = buf
            self.caches = self._inject_seed(self.caches, padded,
                                            np.int32(slot), np.int32(ctx))
            self.temps[slot] = g.params.temperature
            self.top_ks[slot] = g.params.top_k
            self.pos[slot] = ctx
            last = g.output[r.generated - 1]
            if self._async:
                eos = -1 if g.params.eos_token is None \
                    else g.params.eos_token
                one = np.asarray([last], np.int32)
                self._dev = self._seed_slots(
                    self._dev, np.asarray([slot], np.int32),
                    jnp.asarray(one), jnp.asarray(one),
                    np.zeros(1, bool), np.asarray([ctx], np.int32),
                    np.asarray([g.params.temperature], np.float32),
                    np.asarray([g.params.top_k], np.int32),
                    np.asarray([eos], np.int32))
            else:
                self.last_tok[slot] = last
            t_in = sched.cost.swap_in_time(ctx)    # in leg charged here
            sched.pending_extra_time += t_in
            r.swap_time += t_in
            self.n_swap_restores += 1
        return left

    def _guard_step(self, now: float) -> None:
        """Watermark-guard observation at a window boundary: under
        pressure, proactively swap the heaviest waiting GTs out (their
        pages are captured immediately — slot and KVC free before this
        iteration's admissions run); on release, give held requests back
        to the admission path. Only runs when ``_mega_left == 0``, so a
        K=8 fused run observes the same occupancy sequence as K=1."""
        sched = self.scheduler
        if sched.kvc.total_blocks <= 0:
            return
        if self.guard.observe(sched.kvc.allocated_frac):
            for v in sched.swap_victims(self.ecfg.guard_max_swaps):
                sched.guard_swap_out(v, now)
                slot = self.slot_of.pop(v.rid, None)
                if slot is not None:
                    self.free_slots.append(slot)
                    self._chunk_progress.pop(v.rid, None)
                    self._rec_state.pop(v.rid, None)
                    self._swap_out(v.rid, slot)
        elif sched.swap_hold:
            sched.release_swap_holds()

    def squeeze_kvc(self, frac: float) -> int:
        """Chaos ``squeeze``: permanently remove ``frac`` of the KVC
        capacity. Free blocks go immediately; the remainder is harvested
        as live allocations free (``BlockKVC.pending_shrink``), so no
        holder is evicted mid-decode. Deferred while a fused megastep
        window is open — eating free blocks mid-window could invalidate
        the extension headroom the precomputed rows were certified
        against. Returns blocks removed immediately (0 when deferred)."""
        if self._mega_left > 0:
            self._pending_squeeze += float(frac)
            return 0
        kvc = self.scheduler.kvc
        return kvc.shrink(int(kvc.capacity_tokens * frac))

    # ------------------------------------------------------------------ #
    def _is_ring(self, kind: str, sub) -> bool:
        """A cache row is a sliding-window ring buffer when its capacity
        equals the window (shared-attention caches are always full-size)."""
        win = self.cfg.sliding_window
        return kind == ATTN and win is not None and sub["k"].shape[2] == win

    @staticmethod
    def _ring_index(plen, s_idx, C):
        """Within-sequence source index for seeding a C-slot ring buffer
        from a plen-token prefill: token p of the real tail lands at ring
        slot p % C; rows with plen <= C keep identity placement."""
        return jnp.where(plen > C,
                         (plen - C) + jnp.mod(s_idx - plen, C),
                         jnp.minimum(s_idx, jnp.maximum(plen - 1, 0)))

    def _seed_fn(self, caches, pf_caches, slots, lens):
        """Scatter a whole prefill batch into the decode caches at once.

        slots (Bb,) int32 destination rows; pad rows carry ``max_batch``
        (past-the-end, dropped via mode="drop"); lens (Bb,) true context
        lengths (pad positions beyond them carry junk that decode masking
        never reads).
        """
        def seq_scatter(dst, src, ring):
            # dst (L, B, C, K, hd); src (L, Bb, S, K, hd)
            C, S = dst.shape[2], src.shape[2]
            s_idx = jnp.arange(C)[None, :]                      # (1, C)
            plen = lens[:, None]                                # (Bb, 1)
            if ring and S > C:
                j = self._ring_index(plen, s_idx, C)
            else:
                # identity placement; slots beyond S (or beyond plen, for
                # padded prefill) hold junk that decode masking never reads
                j = jnp.broadcast_to(jnp.minimum(s_idx, S - 1),
                                     (src.shape[1], C))
            rows = jnp.take_along_axis(
                src, j[None, :, :, None, None], axis=2)
            return dst.at[:, slots].set(rows.astype(dst.dtype), mode="drop")

        def plain_scatter(dst, src):
            return dst.at[:, slots].set(src.astype(dst.dtype), mode="drop")

        out = {}
        for kind, sub in caches.items():
            if kind in (ATTN, "shared"):
                ring = self._is_ring(kind, sub)
                out[kind] = {n: seq_scatter(sub[n], pf_caches[kind][n], ring)
                             for n in ("k", "v")}
            else:
                out[kind] = jax.tree.map(plain_scatter, sub, pf_caches[kind])
        return out

    def _seed_packed_fn(self, caches, pf_caches, slots, seg, pos, lens):
        """Seed decode caches from a token-packed prefill by scattering
        the wave's real tokens: token t of segment seg[t] lands in row
        slots[seg[t]] at slot pos[t] (mod C for a ring buffer, where only
        a prompt's last C tokens are kept). Pad tokens (seg -1) and pad
        rows (slot ``max_batch``) are dropped. Only the T packed tokens
        move — no (rows, capacity) gather is materialized — and slots past
        a prompt's length keep whatever they held, which decode masking
        never reads. seg/pos (1, T); slots/lens (Bb,)."""
        seg, pos = seg[0], pos[0]
        real = seg >= 0
        item = jnp.where(real, seg, 0)
        out = {}
        for kind, sub in caches.items():
            assert kind in (ATTN, "shared"), \
                "packed prefill is gated to attention-only stacks"
            C = sub["k"].shape[2]
            keep = real & (pos >= lens[item] - C)
            row = jnp.where(keep, slots[item], self.max_batch)
            out[kind] = {n: sub[n].at[:, row, pos % C].set(
                pf_caches[kind][n][:, 0].astype(sub[n].dtype), mode="drop")
                for n in ("k", "v")}
        return out

    # ------------------------------------------------------------------ #
    def _run_prefill(self, items, now: float, missing=()) -> None:
        """Execute an iteration's PT items and seed their cache slots.

        Whole prompts (plus ``missing`` recompute re-prefills) run as ONE
        call: token-packed (flattened with a block-diagonal segment mask —
        no batch or length padding) when enabled, else padded
        (max_batch, seq_bucket) when the model tolerates padding;
        otherwise one exact-shape call per item. Partial (chunked) grants
        route through ``_run_chunk_items`` — one prefix-attending call per
        chunk.
        """
        whole = [(r, r.prompt_len) for r in missing]
        chunked = []
        for r, chunk in items:
            if (r.rid not in self._chunk_progress and r.prompt_done == 0
                    and chunk >= r.prompt_len):
                whole.append((r, chunk))
            else:
                chunked.append((r, chunk))
        if whole:
            self.n_prefill_waves += 1
            groups = [whole] if self._pad_prefill \
                else [[it] for it in whole]
            for group in groups:
                self._prefill_group(group, now)
        if chunked:
            self._run_chunk_items(chunked, now)

    def _prefill_group(self, group, now: float) -> None:
        ctxs, slots = [], []
        for r, chunk in group:
            assert chunk == r.prompt_len, \
                "partial chunks are routed through _run_chunk_items"
            g = self.requests[r.rid]
            # after an offload-free preemption the context to recompute is
            # prompt + generated-so-far MINUS the newest token: normal
            # decode writes token t's KV only when t is fed as the next
            # step's input, so the newest token's KV was never in cache —
            # it stays the pending decode input (seeding it too would make
            # the model see it at two positions and shift the stream)
            ctxs.append(list(g.prompt) + g.output[:max(0, r.generated - 1)])
            slot = self.free_slots.pop()
            self.slot_of[r.rid] = slot
            self.temps[slot] = g.params.temperature
            self.top_ks[slot] = g.params.top_k
            slots.append(slot)
        n = len(group)
        lens_true = [len(c) for c in ctxs]
        maxlen = max(lens_true)
        if self._pad_prefill:
            Bb = self.max_batch
        else:
            Bb = n
        # pad rows: len 1 (safe gather), scatter to row `max_batch` —
        # out of bounds, dropped via mode="drop"
        lens = np.ones(Bb, np.int32)
        slot_arr = np.full(Bb, self.max_batch, np.int32)
        for i in range(n):
            lens[i] = lens_true[i]
            slot_arr[i] = slots[i]
        if self._packed:
            last_idx = np.zeros(Bb, np.int32)
            Tb = seq_bucket(sum(lens_true))
            toks = np.zeros((1, Tb), np.int32)
            pos = np.zeros((1, Tb), np.int32)
            seg = np.full((1, Tb), -1, np.int32)
            off = 0
            for i, ctx in enumerate(ctxs):
                L = lens_true[i]
                toks[0, off:off + L] = ctx
                pos[0, off:off + L] = np.arange(L)
                seg[0, off:off + L] = i
                off += L
                last_idx[i] = off - 1
            self._prefill_shapes.add((1, Tb))
            pos, seg = jnp.asarray(pos), jnp.asarray(seg)
            last_logits, pf_caches = self._prefill_packed(
                self.params, jnp.asarray(toks), pos, seg,
                jnp.asarray(last_idx))
            self.caches = self._seed_packed(
                self.caches, pf_caches, jnp.asarray(slot_arr), seg, pos,
                jnp.asarray(lens))
        else:
            if self._pad_prefill:
                # pow2 bucket, clamped to capacity (a single extra bucket
                # shape) so the padded shape never exceeds the cache it seeds
                Sb = seq_bucket(maxlen)
                if Sb > self.capacity:
                    Sb = max(maxlen, self.capacity)
            else:
                Sb = maxlen
            toks = np.zeros((Bb, Sb), np.int32)
            for i, ctx in enumerate(ctxs):
                toks[i, :len(ctx)] = ctx
            self._prefill_shapes.add((Bb, Sb))
            last_logits, pf_caches = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens))
            self.caches = self._seed(self.caches, pf_caches,
                                     jnp.asarray(slot_arr),
                                     jnp.asarray(lens))
        if self._async:
            # consume the carried device key — same stream as the sync
            # path's self.key, no host materialization
            key, sk = jax.random.split(self._dev["key"])
            self._dev = dict(self._dev, key=key)
        else:
            self.key, sk = jax.random.split(self.key)
        temps = np.zeros(Bb, np.float32)
        top_ks = np.zeros(Bb, np.int32)
        eos = np.full(Bb, -1, np.int32)
        for i, (r, _) in enumerate(group):
            g = self.requests[r.rid]
            temps[i] = g.params.temperature
            top_ks[i] = g.params.top_k
            eos[i] = -1 if g.params.eos_token is None else g.params.eos_token
        first = sample_per_request(last_logits, sk, temps, top_ks)
        if self._async:
            # device path: the first token never touches the host here —
            # it is scattered into the carried slot state and drained with
            # the regular lag-N ring
            fallback = np.zeros(Bb, np.int32)
            use_first = np.zeros(Bb, bool)
            mapping: List[Tuple[int, int]] = []
            for i, (r, _) in enumerate(group):
                g = self.requests[r.rid]
                self.pos[slots[i]] = lens[i]
                if r.generated == 0:
                    # the PT iteration produces the first response token (§1)
                    use_first[i] = True
                    mapping.append((i, r.rid))
                else:
                    fallback[i] = g.output[r.generated - 1]
            self._dev = self._seed_slots(
                self._dev, jnp.asarray(slot_arr), first,
                jnp.asarray(fallback), jnp.asarray(use_first),
                jnp.asarray(lens), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(eos))
            if mapping:
                self._enqueue_drain(first, None, mapping)
        else:
            first_np = np.asarray(first)
            for i, (r, _) in enumerate(group):
                g = self.requests[r.rid]
                slot = slots[i]
                self.pos[slot] = lens[i]
                if r.generated == 0:
                    # the PT iteration produces the first response token (§1)
                    tok = int(first_np[i])
                    g.output.append(tok)
                    self.last_tok[slot] = tok
                else:
                    self.last_tok[slot] = g.output[r.generated - 1]

    # ------------------------------------------------------------------ #
    def _run_chunk_items(self, items, now: float) -> None:
        """Execute partial-prompt (chunked) PT grants. A wave of >= 2
        grants runs as ONE token-packed call with per-segment prefix
        views (``_exec_chunks_packed``, the default); otherwise each
        chunk runs as its own call — attending over the request's
        already-seeded cache prefix (attention-pure stacks), resuming the
        carried recurrent-state snapshot (pure-recurrent stacks), or
        recomputing the whole prefix (the reference path). Only the chunk
        that completes the prompt samples the first response token;
        earlier chunks just extend the cache."""
        infos = []
        for r, chunk in items:
            g = self.requests[r.rid]
            # after an offload-free preemption the context to recompute is
            # prompt + the generated tail minus the newest token (whose KV
            # was never written — it stays the pending decode input, see
            # _prefill_group); the scheduler's grants cover prompt_len
            # tokens, so the tail rides the chunk completing the prompt
            ctx = list(g.prompt) + g.output[:max(0, r.generated - 1)]
            start = self._chunk_progress.get(r.rid, 0)
            completing = r.prompt_done + chunk >= r.prompt_len
            end = len(ctx) if completing else start + chunk
            assert end <= self.capacity, "chunk exceeds cache capacity"
            if r.rid not in self.slot_of:
                slot = self.free_slots.pop()
                self.slot_of[r.rid] = slot
                self.temps[slot] = g.params.temperature
                self.top_ks[slot] = g.params.top_k
            slot = self.slot_of[r.rid]
            self.n_prefill_chunks += 1
            infos.append((r, ctx, start, end, slot, completing))
        if self._chunk_packed and len(infos) >= 2:
            lasts = self._exec_chunks_packed(infos)
        else:
            lasts = []
            for r, ctx, start, end, slot, completing in infos:
                self.n_chunk_calls += 1
                self.max_chunk_items_per_call = max(
                    self.max_chunk_items_per_call, 1)
                if self._chunk_incremental:
                    lasts.append(self._exec_chunk_incremental(
                        ctx, start, end, slot))
                elif self._chunk_rec:
                    lasts.append(self._exec_chunk_state(
                        ctx, start, end, slot, r.rid))
                else:
                    lasts.append(self._exec_chunk_recompute(ctx, end, slot))
        finals = []
        for (r, ctx, start, end, slot, completing), last in zip(infos,
                                                                lasts):
            self._chunk_progress[r.rid] = end
            if completing:
                del self._chunk_progress[r.rid]
                if self._chunk_rec:
                    # the carried snapshot becomes the decode-cache row
                    states = self._rec_state.pop(r.rid)
                    self.caches = self._seed(
                        self.caches, states,
                        jnp.asarray(np.array([slot], np.int32)),
                        jnp.asarray(np.array([end], np.int32)))
                finals.append((r, slot, last, end))
        if not finals:
            return
        # the completing chunks' first-token sampling mirrors
        # _prefill_group: one key split per call, same carried stream
        if self._async:
            key, sk = jax.random.split(self._dev["key"])
            self._dev = dict(self._dev, key=key)
        else:
            self.key, sk = jax.random.split(self.key)
        n = len(finals)
        temps = np.zeros(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        eos = np.full(n, -1, np.int32)
        lens = np.zeros(n, np.int32)
        slot_arr = np.zeros(n, np.int32)
        for i, (r, slot, _, end) in enumerate(finals):
            g = self.requests[r.rid]
            temps[i] = g.params.temperature
            top_ks[i] = g.params.top_k
            eos[i] = -1 if g.params.eos_token is None else g.params.eos_token
            lens[i] = end
            slot_arr[i] = slot
        first = sample_per_request(jnp.stack([f[2] for f in finals]), sk,
                                   temps, top_ks)
        if self._async:
            fallback = np.zeros(n, np.int32)
            use_first = np.zeros(n, bool)
            mapping: List[Tuple[int, int]] = []
            for i, (r, slot, _, end) in enumerate(finals):
                self.pos[slot] = end
                if r.generated == 0:
                    use_first[i] = True
                    mapping.append((i, r.rid))
                else:
                    fallback[i] = self.requests[r.rid].output[r.generated - 1]
            self._dev = self._seed_slots(
                self._dev, jnp.asarray(slot_arr), first,
                jnp.asarray(fallback), jnp.asarray(use_first),
                jnp.asarray(lens), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(eos))
            if mapping:
                self._enqueue_drain(first, None, mapping)
        else:
            first_np = np.asarray(first)
            for i, (r, slot, _, end) in enumerate(finals):
                g = self.requests[r.rid]
                self.pos[slot] = end
                if r.generated == 0:
                    tok = int(first_np[i])
                    g.output.append(tok)
                    self.last_tok[slot] = tok
                else:
                    self.last_tok[slot] = g.output[r.generated - 1]

    def _exec_chunks_packed(self, infos):
        """All of an iteration's chunk grants in ONE prefill dispatch: the
        packed token axis concatenates every chunk with per-segment
        absolute positions and segment ids; the key axis prepends each
        segment's own cache-prefix view with per-slot positions
        (POS_INVALID beyond the seeded prefix — first chunks have empty
        views). Only the shared axes are pow2-rounded, so compile count
        stays logarithmic, and pad tokens imply no cache slots (the seed
        scatter drops them). Returns per-segment last-token logits."""
        n = len(infos)
        starts = [i[2] for i in infos]
        lens = [i[3] - i[2] for i in infos]
        Tb = seq_bucket(sum(lens))
        # prefix-view width: pow2 bucket of the deepest seeded prefix,
        # clamped to the cache capacity (chunk grants never reach past it)
        Cp = seq_bucket(max(max(starts), 1))
        if Cp > self.capacity:
            Cp = self.capacity
        toks = np.zeros((1, Tb), np.int32)
        pos = np.zeros((1, Tb), np.int32)
        seg = np.full((1, Tb), -1, np.int32)
        last_idx = np.zeros(n, np.int32)
        offs = np.zeros(n, np.int32)
        off = 0
        for i, (r, ctx, start, end, slot, completing) in enumerate(infos):
            L = end - start
            toks[0, off:off + L] = ctx[start:end]
            pos[0, off:off + L] = start + np.arange(L)
            seg[0, off:off + L] = i
            offs[i] = off
            last_idx[i] = off + L - 1
            off += L
        ppos = np.full((n, Cp), POS_INVALID, np.int32)
        pseg = np.repeat(np.arange(n, dtype=np.int32)[:, None], Cp, axis=1)
        for i, s in enumerate(starts):
            ppos[i, :min(s, Cp)] = np.arange(min(s, Cp))
        # seed-scatter indices: chunk i's tokens land at cache positions
        # [start_i, start_i + len_i); pad columns index capacity (dropped)
        W = min(seq_bucket(max(lens)), Tb)
        w_idx = np.arange(W)[None, :]
        lens_a = np.asarray(lens, np.int32)[:, None]
        starts_a = np.asarray(starts, np.int32)[:, None]
        dst_idx = np.where(w_idx < lens_a, starts_a + w_idx, self.capacity)
        src_idx = offs[:, None] + np.minimum(w_idx, lens_a - 1)
        slots = np.asarray([i[4] for i in infos], np.int32)
        self._prefill_shapes.add((1, Tb))
        self.n_chunk_calls += 1
        self.max_chunk_items_per_call = max(self.max_chunk_items_per_call,
                                            n)
        self.caches, last = self._chunks_packed(
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(seg), jnp.asarray(ppos.reshape(1, n * Cp)),
            jnp.asarray(pseg.reshape(1, n * Cp)), jnp.asarray(slots),
            jnp.asarray(last_idx), jnp.asarray(src_idx.astype(np.int32)),
            jnp.asarray(dst_idx.astype(np.int32)))
        return [last[i] for i in range(n)]

    def _exec_chunk_state(self, ctx, start: int, end: int, slot: int,
                          rid: int):
        """Chunk prefill for pure-recurrent stacks: resume from the
        carried per-request state snapshot — O(n) total across chunks
        instead of the recompute fallback's O(n^2). The snapshot seeds
        the decode cache row when the prompt completes."""
        L = end - start
        toks = np.zeros((1, L), np.int32)
        toks[0, :] = ctx[start:end]
        self._prefill_shapes.add((1, L))
        states = self._rec_state.pop(rid, None)
        if states is None:
            # first chunk: a plain exact-shape prefill from the zero state
            last, states = self._prefill(
                self.params, jnp.asarray(toks),
                jnp.asarray(np.array([L], np.int32)))
            last = last[0]
        else:
            states, last = self._rec_chunk(self.params, states,
                                           jnp.asarray(toks))
        self._rec_state[rid] = states
        return last

    def _exec_chunk_incremental(self, ctx, start: int, end: int,
                                slot: int):
        """Run ctx[start:end) as a prefix-attending chunk and seed its K/V
        into the slot's cache row in one donated program."""
        L = end - start
        Sb = seq_bucket(L)
        # tail-chunk cap: the pow2 round-up must never imply cache slots
        # (and thus KVC pages) past what the scheduler granted — clamp the
        # padded shape to the capacity remaining after ``start``
        if start + Sb > self.capacity:
            Sb = max(L, self.capacity - start)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :L] = ctx[start:end]
        pos = (start + np.arange(Sb, dtype=np.int32))[None]
        self._prefill_shapes.add((1, Sb))
        self.caches, last = self._chunk_prefill(
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(pos),
            np.int32(slot), np.int32(start), np.int32(L))
        return last

    def _exec_chunk_recompute(self, ctx, end: int, slot: int):
        """Chunk fallback with no resumable prefix view (recurrent stacks,
        or ``incremental_chunk_prefill=False``): re-run positions [0, end)
        and reseed the whole cache row."""
        Sb = end
        if self._pad_prefill:
            Sb = seq_bucket(end)
            if Sb > self.capacity:
                Sb = max(end, self.capacity)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :end] = ctx[:end]
        lens = np.array([end], np.int32)
        self._prefill_shapes.add((1, Sb))
        last_logits, pf_caches = self._prefill(
            self.params, jnp.asarray(toks), jnp.asarray(lens))
        self.caches = self._seed(self.caches, pf_caches,
                                 jnp.asarray(np.array([slot], np.int32)),
                                 jnp.asarray(lens))
        return last_logits[0]

    # ------------------------------------------------------------------ #
    def _run_decode(self, reqs: Sequence[Request], now: float) -> None:
        """Legacy sync decode: one host sync per iteration for the sampled
        batch, then per-request host reads. Kept as the reference the
        async path is equivalence-tested against."""
        if not reqs:
            return
        active = np.zeros(self.max_batch, bool)
        for r in reqs:
            active[self.slot_of[r.rid]] = True
        toks = jnp.asarray(self.last_tok, jnp.int32)[:, None]
        pos = jnp.asarray(self.pos, jnp.int32)
        logits, self.caches = self._decode(self.params, toks, pos,
                                           self.caches, jnp.asarray(active))
        self.key, sk = jax.random.split(self.key)
        # inactive slots are likewise masked to greedy (temp 0) sampling
        # and their tokens never read back
        temps = np.where(active, self.temps, 0.0).astype(np.float32)
        top_ks = np.where(active, self.top_ks, 0).astype(np.int32)
        # this materialization waits on the iteration that was just
        # dispatched — the per-iteration blocking sync the async path removes
        self.sync_counts["drain_blocking"] += 1
        new_toks = np.asarray(sample_per_request(
            logits, sk, jnp.asarray(temps), jnp.asarray(top_ks)))
        self.decode_iters += 1
        self.n_decode_dispatches += 1
        for r in reqs:
            slot = self.slot_of[r.rid]
            g = self.requests[r.rid]
            tok = int(new_toks[slot])
            g.output.append(tok)
            self.pos[slot] += 1
            self.last_tok[slot] = tok
            if g.params.eos_token is not None and tok == g.params.eos_token:
                self.scheduler.notify_eos(r, r.generated + 1)

    def _run_decode_async(self, plan, now: float) -> None:
        """Fused device-resident decode. The host builds the (B,) active
        mask, splits the RNG key (an async device op, identical key stream
        to the sync path) and dispatches the donated fused step; sampled
        tokens land in the lag-N drain ring. EOS flags are only read back
        when an active request actually has an ``eos_token`` — the clamp
        must reach the scheduler at the iteration EOS fires to keep its
        decisions bitwise-equal to the sync path.

        When the scheduler proves a K-iteration horizon with fixed batch
        membership (``decode_horizon``), all K iterations run as ONE
        megastep dispatch and the following K-1 calls are pure host replay
        against the precomputed (K, B) token window."""
        reqs = plan.decode_reqs
        if not reqs:
            return
        # drain first: entries had a whole scheduler cycle to finish on
        # device, so lag-expired drains are copies, not waits
        self._drain_tokens()
        if self._mega_left > 0:
            self._consume_mega_row(reqs)
            return
        active = np.zeros(self.max_batch, bool)
        eos_possible = False
        for r in reqs:
            active[self.slot_of[r.rid]] = True
            if self.requests[r.rid].params.eos_token is not None:
                eos_possible = True
        temps_m = np.where(active, self.temps, 0.0)
        need_sample = bool(np.any(temps_m > 0.0))
        need_topk = need_sample and bool(
            np.any(np.where(active, self.top_ks, 0) > 0))
        # the active mask only changes on admission/completion/preemption;
        # steady state reuses the cached device copy (no transfer dispatch)
        ab = active.tobytes()
        if ab != self._active_bytes:
            self._active_bytes = ab
            self._active_dev = self._place(active)
        # every ring entry is one dispatched iteration's token row, and no
        # window is open here, so the ring holds all undrained iterations
        self.ahead_iters_sum += len(self._pending_drain)
        self.n_ahead_samples += 1
        K = self.scheduler.decode_horizon(plan, self._mega_max)
        if K > 1:
            # under pressure (waiters certified KVC-blocked) an EOS
            # completion frees KVC the K=1 path would grant next
            # iteration — the device loop exits right after the EOS
            # iteration and the host truncates the window to match
            sched = self.scheduler
            stop_on_eos = eos_possible and bool(sched.pt_queue
                                                or sched.gt_queue)
            self.caches, self._dev, self._mega_toks, eos_buf = self._mega(
                self.params, self.caches, self._dev, self._active_dev,
                np.int32(K), need_sample, need_topk, stop_on_eos)
            self.n_decode_dispatches += 1
            if eos_possible:
                # ONE blocking readback per window (the per-iteration path
                # pays one per iteration); the scheduler still sees each
                # EOS at the replay iteration it fired
                self.sync_counts["eos_flags"] += 1
                self._mega_eos = np.asarray(eos_buf)
                if stop_on_eos:
                    slots = [self.slot_of[r.rid] for r in reqs]
                    hit = self._mega_eos[:K, slots].any(axis=1)
                    if hit.any():
                        K = int(hit.argmax()) + 1
            else:
                self._mega_eos = None
            self._mega_row = -1
            self._mega_left = K
            self._consume_mega_row(reqs)
            return
        self.caches, self._dev, toks, eos_hit = self._fused(
            self.params, self.caches, self._dev, self._active_dev,
            need_sample, need_topk)
        self.n_decode_dispatches += 1
        self.decode_iters += 1
        self._enqueue_drain(
            toks, None, [(self.slot_of[r.rid], r.rid) for r in reqs])
        if eos_possible:
            self.sync_counts["eos_flags"] += 1
            flags = np.asarray(eos_hit)
            for r in reqs:
                if flags[self.slot_of[r.rid]]:
                    self.scheduler.notify_eos(r, r.generated + 1)

    def _consume_mega_row(self, reqs: Sequence[Request]) -> None:
        """One host-replay iteration of a fused megastep window: push the
        iteration's precomputed token row into the drain ring — mapped
        through the *current* plan, so EOS-shrunken membership stays
        exact — and deliver the row's EOS flags to the scheduler."""
        self._mega_row += 1
        self._mega_left -= 1
        i = self._mega_row
        self.decode_iters += 1
        self._enqueue_drain(
            self._mega_toks, i,
            [(self.slot_of[r.rid], r.rid) for r in reqs],
            new_dispatch=(i == 0))
        if self._mega_eos is not None:
            flags = self._mega_eos[i]
            for r in reqs:
                if flags[self.slot_of[r.rid]]:
                    self.scheduler.notify_eos(r, r.generated + 1)

    def _enqueue_drain(self, toks, row, mapping,
                       new_dispatch: bool = True) -> None:
        """Push one sampled-token entry into the readback ring and
        classify it NOW, from the dispatch sequence alone. An entry whose
        lag window (the last ``readback_lag`` enqueues) already holds an
        older distinct dispatch can only ever wait as backpressure — by
        the time it is lag-expired the device has newer work queued. An
        entry whose whole lag window is its own dispatch (megastep replay
        rows) — or nothing — drains as a lag-aged copy. Neither depends
        on ``is_ready()`` timing, so the per-category counts are
        reproducible across machines (the drain-time classification this
        replaces was not; only the total was)."""
        if new_dispatch:
            self._drain_seq += 1
        seq = self._drain_seq
        if any(s != seq for s in self._recent_drain_seqs):
            self.sync_counts["drain_backpressure"] += 1
        else:
            self.sync_counts["drain_ready"] += 1
        self._recent_drain_seqs.append(seq)
        self._pending_drain.append((toks, row, mapping))

    def _drain_tokens(self, force: bool = False) -> None:
        """Materialize pending sampled-token batches older than the lag.

        Steady state: an entry ``readback_lag`` iterations old has long
        finished on device, so the readback is a copy, not a wait — the
        engine only accepts a potentially-waiting drain when the number of
        undrained *dispatches* (distinct buffers — a K-row megastep window
        counts once) exceeds ``max_pending``, or a flush is forced
        (completion, preemption, idle, end of run). ``is_ready()`` only
        steers this pop policy (performance); sync *accounting* happened
        at enqueue time (``_enqueue_drain``), so counts are deterministic.

        All expired entries materialize through ONE batched
        ``jax.device_get`` (deduplicated by buffer), not one copy per
        entry."""
        dq = self._pending_drain
        lag = 0 if force else self.ecfg.readback_lag
        batch = []
        while len(dq) > lag:
            toks, row, mapping = dq[0]
            if not toks.is_ready() and not force and len(
                    {id(t) for t, _, _ in dq}) <= self.ecfg.max_pending:
                break
            dq.popleft()
            batch.append((toks, row, mapping))
        if not batch:
            return
        uniq: Dict[int, jax.Array] = {}
        for toks, _, _ in batch:
            uniq.setdefault(id(toks), toks)
        with span("engine.drain_wait"):
            mats = jax.device_get(list(uniq.values()))
        mat_of = dict(zip(uniq.keys(), mats))
        for toks, row, mapping in batch:
            arr = mat_of[id(toks)]
            if row is not None:
                arr = arr[row]
            for r_, rid in mapping:
                self.requests[rid].output.append(int(arr[r_]))
            self.n_tokens_drained += len(mapping)

    # ------------------------------------------------------------------ #
    def step(self, now: Optional[float] = None) -> int:
        """One engine iteration. Returns number of completions."""
        now = time.monotonic() if now is None else now
        if self._mega_left == 0 and (self._arrivals or self._pending_injects
                                     or self._pending_aborts):
            # a fused window just drained: apply the aborts it deferred
            # (freed slots/KVC are then visible to the injects/arrivals),
            # then deliver arrivals and peer KV injections
            with span("engine.admit"):
                for rid, t_ab, reason in self._pending_aborts:
                    self._apply_abort(rid, t_ab, reason)
                self._pending_aborts.clear()
                for payload, t_in in self._pending_injects:
                    self._apply_inject(payload, t_in)
                self._pending_injects.clear()
                for r, t_arr in self._arrivals:
                    self.scheduler.on_arrival(r, t_arr)
                    self.requests[r.rid].t_admitted = now
                self._arrivals.clear()
        if self._mega_left == 0 and self._pending_squeeze:
            kvc = self.scheduler.kvc
            kvc.shrink(int(kvc.capacity_tokens * self._pending_squeeze))
            self._pending_squeeze = 0.0
        if self.guard is not None and self._mega_left == 0:
            self._guard_step(now)
        with span("engine.form_batch"):
            plan = self.scheduler.form_batch(now)
        if self.scheduler.infeasible_shed:
            # rung 4: the scheduler cancelled requests a squeeze made
            # permanently inadmissible *here* — surface each as a
            # terminal shed, or (fleet hand-back enabled) cancel locally
            # and park the request non-terminal for the fleet's
            # shed-retry tier to re-route to a peer that can still fit it
            shed, self.scheduler.infeasible_shed = \
                self.scheduler.infeasible_shed, []
            for r in shed:
                self.abort(r.rid, now, "kvc-infeasible")
                g = self.requests.get(r.rid)
                if g is not None and g.status == "aborted":
                    if self.fleet_shed_handback:
                        g.status = None
                        g.fail_reason = None
                        self.n_aborted -= 1
                        self.requests.pop(r.rid, None)
                        self.shed_handback.append(g)
                    else:
                        g.status = "shed"
                        self.n_aborted -= 1
                        self.n_shed += 1
        if plan.empty:
            if self._mega_left:
                # every window request completed early (EOS inside the
                # window): the remaining precomputed rows belong to no one
                self._mega_left = 0
                self._mega_toks = self._mega_eos = None
            if self._pending_drain:
                self.sync_counts["flush"] += 1
                self._drain_tokens(force=True)
            if self.metrics is not None:
                self.metrics.on_step(self, now)
            return 0
        # GTs rescheduled after a swap-style preemption or deadlock-relief
        # eviction arrive with their KV "in host memory". With a live
        # host-pool image they are *restored* — pages re-seeded, zero
        # recompute (rung 2); otherwise they are recomputed like an
        # offload-free re-prefill (prompt + generated so far), riding the
        # iteration's prefill wave (rung 3)
        missing = [r for r in plan.decode_reqs if r.rid not in self.slot_of]
        if self._mega_left > 0:
            assert not plan.prompt_items and not missing, \
                "megastep horizon violated: admission inside a fused window"
        if missing and self._pending_drain:     # ctx rebuild reads g.output
            self.sync_counts["flush"] += 1
            self._drain_tokens(force=True)
        if missing:
            missing = self._swap_in(missing, now)
        with span("engine.prefill"):
            self._run_prefill(plan.prompt_items, now, missing=missing)
        # the decode span carries the run-ahead counters as they stood
        # before this step, so a trace reads their change over a window
        with span("engine.decode", ahead_iters=self.ahead_iters_sum,
                  ahead_samples=self.n_ahead_samples):
            if self._async:
                self._run_decode_async(plan, now)
            else:
                self._run_decode(plan.decode_reqs, now)
        with span("engine.finish"):
            done = self._finish(now)
        if self.metrics is not None:
            self.metrics.on_step(self, now)
        return len(done)

    def _finish(self, now: float) -> List[Request]:
        """Close the iteration in the scheduler, then settle what it
        decided: terminal state of completed requests, slots of completed
        and preempted ones, and the forced drain their outputs need.
        Returns the requests completed in this iteration."""
        before = len(self.scheduler.completed)
        self.scheduler.finish_iteration(now)
        done = self.scheduler.completed[before:]
        freed = False
        for r in done:
            g = self.requests[r.rid]
            if g.finished:
                # first-writer-wins: another engine (or the fleet's
                # redelivery fast path) already wrote this request's
                # terminal state — suppress the second writer and count
                # it; the invariant audit flags any non-zero count
                self.n_dup_completions += 1
            else:
                g.t_done = r.t_complete
                g.t_scheduled = r.t_start_exec
                g.t_first_sampled = r.t_first_token
                g.status = "completed"
            slot = self.slot_of.pop(r.rid, None)
            if slot is not None:
                self.free_slots.append(slot)
                freed = True
        # preempted/evicted requests (KVC freed by the scheduler) lose
        # their slot; queued GTs keep theirs — their KV is live. Before a
        # victim's slot is recycled its cache pages are offloaded to the
        # host pool (rung 2), so the next schedule restores instead of
        # recomputing; nothing reuses the slot until next step's prefill,
        # so the post-free capture still reads the victim's pages
        for rid in list(self.slot_of):
            if rid not in self.scheduler.kvc.allocs:
                slot = self.slot_of.pop(rid)
                self.free_slots.append(slot)
                self._chunk_progress.pop(rid, None)
                self._rec_state.pop(rid, None)
                self._swap_out(rid, slot)
                freed = True
        if freed and self._pending_drain:
            # completed outputs must be materialized before t_done is
            # observable, and a preempted request rebuilds its recompute
            # context from g.output at the next prefill
            self.sync_counts["flush"] += 1
            self._drain_tokens(force=True)
        return done

    def flush(self) -> None:
        """Force-drain the token readback ring so every request's
        ``output`` is fully materialized on the host (end of a run, or
        before inspecting outputs mid-stream)."""
        if self._pending_drain:
            self.sync_counts["flush"] += 1
            self._drain_tokens(force=True)

    # ------------------------------------------------------------------ #
    # liveness / diagnostics (serve_stream watchdog, invariant checker)
    # ------------------------------------------------------------------ #
    def progress_state(self) -> tuple:
        """Monotone fingerprint of forward progress: any iteration that
        decodes, prefills, completes, aborts, or accepts work changes it.
        ``serve_stream`` raises ``FleetStalled`` when it freezes while
        ``has_work()`` holds (e.g. a scheduler wedged on an unplaceable
        request)."""
        return (self.decode_iters, self.n_prefill_waves,
                self.n_prefill_chunks, len(self.scheduler.completed),
                self.n_aborted, self.n_kv_injects, self._rid)

    def publish_metrics(self, registry, instance: str = "0") -> None:
        """Publish every engine/scheduler/KVC counter and gauge into a
        ``repro.obs`` registry (the typed publication API — one code path
        for live sampling, stall diagnostics and exit dumps)."""
        from repro.obs import publish_engine
        publish_engine(self, registry, instance)

    def debug_state(self) -> Dict[str, object]:
        """Queue/KVC snapshot for stall diagnostics — derived from a
        registry snapshot (the same publication path live metrics use),
        not a hand-assembled dict, so the two can never disagree."""
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        self.publish_metrics(reg)
        return reg.snapshot().flat()

    def run(self, gen_requests: Sequence[GenRequest],
            arrivals: Optional[Sequence[float]] = None,
            max_steps: int = 100_000, stall_limit: int = 2_000
            ) -> List[GenRequest]:
        """Serve a batch to completion — or, with ``arrivals``, an online
        stream: each request is submitted at its arrival time on the
        engine's iteration clock (the same contract as
        ``EngineFleet.run``)."""
        return serve_stream(self, gen_requests, arrivals, max_steps,
                            stall_limit)


def serve_stream(server, gen_requests: Sequence[GenRequest],
                 arrivals: Optional[Sequence[float]] = None,
                 max_steps: int = 100_000,
                 stall_limit: int = 2_000) -> List[GenRequest]:
    """Drive any submit/step/has_work/flush server (a ``ServingEngine``
    or a ``repro.cluster.EngineFleet``) over an online request stream on
    its iteration clock: submit each request at its arrival time, step
    while there is work, jump the clock across idle gaps, flush the
    readback ring at the end. The single definition keeps both backends'
    ``run(reqs, arrivals)`` semantics from drifting.

    Two robustness contracts live here:

      * a typed ``RequestShed`` from ``submit`` (fleet admission control)
        is caught and the stream continues — the server already recorded
        the terminal ``shed`` state;
      * a no-progress watchdog: ``stall_limit`` consecutive steps whose
        ``progress_state()`` fingerprint never moves (while ``has_work()``
        holds) raise ``FleetStalled`` with per-instance queue/KVC state,
        instead of the pre-fault-tolerance behavior of spinning on
        ``has_work()`` forever. The limit must exceed any legitimate
        quiet period (fault-injected freezes, recovery backoff waits).
    """
    if arrivals is None:
        arrivals = [0.0] * len(gen_requests)
    stream = sorted(zip(gen_requests, arrivals), key=lambda p: p[1])
    fingerprint = getattr(server, "progress_state", None)
    t, i, steps, stalled, last_fp = 0.0, 0, 0, 0, None
    while steps < max_steps:
        submitted = False
        while i < len(stream) and stream[i][1] <= t:
            try:
                server.submit(stream[i][0], float(stream[i][1]))
            except RequestShed:
                pass              # typed fast-fail; terminal state recorded
            i += 1
            submitted = True
        if not server.has_work():
            if i >= len(stream):
                break
            t = max(t, float(stream[i][1]))
            continue
        t += 1.0
        server.step(t)
        steps += 1
        if fingerprint is not None:
            fp = fingerprint()
            if fp == last_fp and not submitted:
                stalled += 1
                if stalled >= stall_limit:
                    dbg = getattr(server, "debug_state", dict)()
                    raise FleetStalled(
                        f"no progress for {stall_limit} consecutive steps "
                        f"with work outstanding (t={t}); per-instance "
                        f"state: {dbg}", debug=dbg)
            else:
                stalled = 0
            last_fp = fp
    server.flush()
    return list(gen_requests)
