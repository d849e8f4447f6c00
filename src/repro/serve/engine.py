"""Serving engines: one-shot ``generate``, the lock-step ``Engine``
baseline, and the continuous-batching ``ContinuousEngine``.

``generate`` is the jittable one-shot core (prefill + ``lax.scan`` decode);
``Engine`` keeps the fixed-slot lock-step shape (every row prefills and
decodes together — still the right tool for encdec caches and for
bit-exactness baselines).  ``ContinuousEngine`` is the serving system:
requests are admitted into recyclable slots mid-flight, each slot carrying
its own per-slot state — an attention KV lane (paged, dense, or
ring-buffer), SSM conv/ssm recurrent state, or both (hymba) — plus a
position counter and sampling params.  Prompts are
prefilled in **bucket-padded chunks interleaved with decode steps** — a
long prompt no longer freezes the running decode lanes for its whole
prefill, and a prompt whose prefix is already resident in the paged pool
starts prefilling *after* the cached blocks instead of recomputing them.
See ``repro.serve.scheduler`` for the request lifecycle,
``repro.serve.paging`` for block/prefix bookkeeping, and
``repro.serve.trace`` for workload replay.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Iterator, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.runtime import global_config
from repro.dist.sharding import (activation_mesh, cache_shardings,
                                 data_sharding, mesh_of, model_shardings)
from repro.nn.attention import UnsupportedCacheError
from repro.serve.paging import PagedCacheManager
from repro.serve.sampling import greedy_tokens, sample_tokens
from repro.serve.scheduler import Completion, Request, Scheduler

# -- telemetry: host spans and compile accounting -----------------------------

#: the engine whose ``step()`` is open on this thread and the ``serve.*``
#: span open inside it: the one compile listener charges compiles to them
_ON_THREAD = threading.local()
#: fires once per program a jit obtains, compiled or loaded from the cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listener_lock = threading.Lock()
_listening = False
#: requests per wait reservoir (queue wait, prefill wait), as ServeMetrics
_WAIT_RESERVOIR = 4096


def _on_compile(event: str, secs: float, **kw) -> None:
    engine = getattr(_ON_THREAD, "engine", None)
    if event == _COMPILE_EVENT and engine is not None:
        engine._programs_compiled += 1
        engine._step_compiles.append(_ON_THREAD.phase)


def _listen_for_compiles() -> None:
    """Register the process-wide compile listener, once."""
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _listening = True


class _span:
    """A ``serve.*`` host span: a ``jax.profiler.TraceAnnotation`` (a
    profile records it on the device trace's clock, so a device idle gap
    can be put down to the span open at its middle) that also names the
    phase a compile on this thread is charged to.  ``engine`` marks the
    outermost span, ``serve.step``, whose engine the compiles count in."""

    __slots__ = ("name", "engine", "_ann", "_prev")

    def __init__(self, name: str, engine=None):
        self.name, self.engine = name, engine

    def __enter__(self):
        self._prev = (getattr(_ON_THREAD, "phase", None),
                      getattr(_ON_THREAD, "engine", None))
        _ON_THREAD.phase = self.name
        if self.engine is not None:
            _ON_THREAD.engine = self.engine
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _ON_THREAD.phase, _ON_THREAD.engine = self._prev


def _p50_p95(values) -> Tuple[float, float]:
    if not values:
        return 0.0, 0.0
    p50, p95 = np.percentile(np.asarray(values, np.float64), (50, 95))
    return float(p50), float(p95)


def generate(model, tokens: jax.Array, cache, *, n_steps: int,
             temperature: float = 0.0, key: Optional[jax.Array] = None):
    """Prefill on ``tokens`` then decode ``n_steps`` tokens.

    Returns (generated (batch, n_steps), final cache)."""
    logits, cache = model.prefill(tokens, cache)
    batch = tokens.shape[0]
    temp = jnp.full((batch,), temperature, jnp.float32)

    if key is None:
        key = jax.random.PRNGKey(0)
    first = sample_tokens(logits[:, -1], temp, key)

    def step(carry, k):
        tok, cache = carry
        logits, cache = model.decode(tok[:, None], cache)
        nxt = sample_tokens(logits[:, -1], temp, k)
        return (nxt, cache), tok

    keys = jax.random.split(jax.random.fold_in(key, 1), n_steps)
    (last, cache), toks = jax.lax.scan(step, (first, cache), keys)
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)[:, :n_steps]
    return out, cache


class Engine:
    """Fixed-slot lock-step batching (the pre-continuous baseline).

    One jitted prefill + one jitted decode step; every row moves together.
    Kept for encdec cache families and as the equivalence baseline for
    ``ContinuousEngine``."""

    def __init__(self, model, cfg, *, batch: int, max_len: int,
                 cache_dtype=jnp.bfloat16, enc_len: Optional[int] = None):
        self.model, self.cfg = model, cfg
        self.batch, self.max_len = batch, max_len
        kwargs = {"enc_len": enc_len} if enc_len is not None else {}
        self._cache0 = model.init_cache(batch, max_len, cfg,
                                        dtype=cache_dtype, **kwargs)
        self._prefill = jax.jit(lambda toks, c: model.prefill(toks, c))
        self._decode = jax.jit(lambda tok, c: model.decode(tok, c))
        self.cache = self._cache0

    def reset(self) -> None:
        self.cache = self._cache0

    def prefill(self, tokens: jax.Array) -> jax.Array:
        logits, self.cache = self._prefill(tokens, self.cache)
        return logits

    def decode_step(self, tok: jax.Array) -> jax.Array:
        logits, self.cache = self._decode(tok, self.cache)
        return logits

    def greedy(self, tokens: jax.Array, n_steps: int) -> jax.Array:
        logits = self.prefill(tokens)
        out = [greedy_tokens(logits[:, -1])]
        for _ in range(n_steps - 1):
            logits = self.decode_step(out[-1][:, None])
            out.append(greedy_tokens(logits[:, -1]))
        return jnp.stack(out, axis=1)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class _SlotArrays(NamedTuple):
    """Per-slot device state: the batched half of the request lifecycle."""

    tok: jax.Array       # (B,) int32 — last sampled token per slot
    active: jax.Array    # (B,) bool — slot holds a live request
    temp: jax.Array      # (B,) float32 — 0 => greedy
    n_gen: jax.Array     # (B,) int32 — tokens generated so far (incl. first)
    max_new: jax.Array   # (B,) int32
    stop_ids: jax.Array  # (B, K) int32, -1 padded


@dataclass
class _PrefillTask:
    """Host mirror of one in-flight chunked prefill."""

    req: Request
    slot: int
    seq: int             # admission order (chunks advance round-robin in seq)
    plen: int
    cached: int          # leading tokens resident via prefix hit (no write)
    consumed: int        # prompt positions fed so far (starts at skip point)
    hit_bids: Tuple[int, ...] = ()   # shared blocks the chunks read
    logits: Optional[jax.Array] = None  # (1, vocab) from the latest chunk
    chunks: int = 0


class ContinuousEngine:
    """Continuous-batching serving engine over a fixed slot batch.

    Requests join and leave mid-flight.  ``step()`` is a small policy
    loop::

        admit  — pop FIFO-pending requests into free slots while the block
                 reservation fits (paged); no compute happens here
        chunk  — advance in-flight prefills in a ROTATING round-robin,
                 one bucket-padded chunk at a time, spending at most
                 ``prefill_chunk_budget`` padded tokens per step (a long
                 prompt's prefill spreads over many steps; the decode
                 lanes below keep moving, and the rotation means a short
                 prompt behind a long one binds in its own step instead
                 of waiting out the whole long prefill)
        bind   — a prefill that consumed its whole prompt samples its first
                 token from the final chunk's logits and joins the decode
                 batch (this is the TTFT moment)
        decode — ONE jitted batched decode step advances every bound slot
                 at its own position; stop/max/cache-full eviction computed
                 in-graph

    **Chunked + bucketed prefill.**  A prompt is consumed ``chunk_size``
    tokens at a time; each span is right-padded to the smallest width in
    ``buckets`` that fits, so the chunk jit compiles at 2–3 widths instead
    of one ``max_prompt_len`` pad (and instead of per-prompt-length
    recompiles).  Chunk K/V rows scatter into the slot's lane (dense) or
    freshly reserved pool blocks (paged) at the chunk's position offset;
    chunk attention sees everything before it, so any chunking of a prompt
    is bit-identical to the monolithic prefill.

    **Prefix-aware admission (paged only).**  Admission asks the
    :class:`~repro.serve.paging.PagedCacheManager` for the longest cached
    block-chain matching the prompt; hit blocks are attached to the slot's
    table and prefill STARTS at the hit boundary — cached prefix compute is
    skipped, not just its memory (when the whole prompt hits, only the
    final token is recomputed to produce first-sample logits).  Freed
    prefix blocks are parked on an LRU (``prefix_retain_blocks``) so hits
    survive idle periods.  A prefill whose hit blocks were registered by a
    still-running prefill waits until the provider publishes them.

    Two KV layouts (``kv_layout``): ``"paged"`` (default) — all slots
    share one pool of ``block_size``-token KV blocks with per-slot block
    tables, reservation-based admission, refcounted prefix sharing;
    ``"dense"`` — per-slot ``max_len`` lanes, kept as the bit-exactness
    baseline.  ``decode_kernel`` (paged only) picks the decode attention:
    ``"reference"`` dense-gather or ``"pallas"`` fused
    :func:`repro.kernels.paged_attention` (interpret mode off-TPU).
    ``prefill_kernel`` (either layout, cache kind ``"kv"`` only) does the
    same for the chunked-prefill attention: ``"reference"`` dense-gather
    or ``"pallas"`` flash :func:`repro.kernels.chunk_attention`.
    Greedy tokens are bit-identical across all of it.

    **Heterogeneous per-slot state.**  The model declares its state
    family through the ``cache_kind(cfg)`` capability probe: ``"kv"``
    (global-attention transformers — both layouts above apply), ``"ring"``
    (sliding-window transformers: per-slot ring lanes, ``slot(p) = p %
    window``), ``"ssm"`` (mamba: per-slot conv/ssm recurrent state), and
    ``"hybrid"`` (hymba: ring lanes + ssm state).  Non-``"kv"`` kinds
    cannot be paged or prefix-cached — the state is either not
    position-addressable (ssm) or O(window) by construction (ring) — so
    admission degrades gracefully: the engine serves them through the
    per-slot layout regardless of ``kv_layout``, with prefix reuse
    auto-off and block reservation skipped.  Stale state from a recycled
    slot never leaks: ring masks exclude lanes the new request has not
    written, and the first prefill chunk zeros the slot's ssm lanes
    in-graph.  Because recurrent/ring state has no out-of-range "parked"
    row, the batched decode step freezes inactive slots by a slot-wise
    select over the cache instead of relying on dropped writes.  Models
    without a probe (whisper enc-dec) are rejected with a structured
    :class:`UnsupportedCacheError` naming the remaining ROADMAP item.

    **Sharded serving** (``mesh``).  On a ``{data, model}`` mesh the
    engine runs SPMD: weights by the Megatron rules, the paged pool's KV
    heads over "model" (blocks stay global), slots over "data", and both
    Pallas kernels shard-local (see ``repro/dist/README.md``).  Without
    a ``mesh`` argument it serves on the mesh the model's weights are
    committed to, if they span more than one device; each cache shard is
    allocated on its own device, so no device ever holds the whole model
    or pool.

    **Speculative decoding** (``draft_model`` + ``spec_k``).  The paper's
    low-rank factorized model (``auto_fact``) drafts ``spec_k`` tokens
    greedily with cheap single-token steps, then the dense model verifies
    all of them in ONE multi-token decode step (k queries under a ``kpos
    <= qpos`` mask — see :meth:`repro.nn.attention.Attention.decode`) and
    the agreeing prefix plus one correction token is emitted.  Every
    emitted token is an argmax of DENSE logits conditioned on previously
    emitted tokens, so greedy output is bit-identical to the plain dense
    engine by construction — the draft quality only moves the acceptance
    rate (speed), never the tokens.  The draft keeps its own cache
    mirroring the verifier's layout (same block tables when paged); both
    length frontiers advance together by the accepted count, and rows past
    the frontier are rewritten before they can be attended.  Greedy-only:
    ``submit`` rejects ``temperature != 0`` when speculation is on.

    Streaming: ``stream()`` yields ``(uid, token, completion|None)`` as
    tokens land (``token`` is ``None`` for a request that finished a step
    without emitting one — cancellation, ``max_steps`` truncation), and
    ``on_token`` (callable ``(uid, token)``) fires inside ``step()`` for
    push-style consumers.  A raising ``on_token`` never corrupts the
    step: the error is swallowed and recorded in ``on_token_errors``.

    **Cancellation.**  ``cancel(uid)`` is thread-safe (the HTTP front
    door calls it from the asyncio event loop while ``step()`` runs in
    an executor thread) and takes effect at the start of the next
    ``step()``, which returns the ``finish_reason="cancelled"``
    :class:`Completion` like any other finish.  A pending request is
    dropped from the queue; a mid-prefill or mid-decode request releases
    its slot, parked write frontier, and every refcounted paged block.
    One wrinkle: a cancelled prefill may have registered prefix blocks
    that later admissions already hit but that its chunks never wrote —
    those dependents are *rewound* to recompute (and publish) the
    orphaned span themselves, so prefix sharing never deadlocks on a
    dead writer (see :meth:`_rewind_dependents`).

    **Priority, deadlines, preemption.**  ``submit`` takes a per-request
    ``priority`` class (0 = most urgent; default 1) and optional
    ``timeout_s``: admission serves the best non-empty class FIFO-within-
    class with a starvation bound (``aging_every`` — see
    :class:`repro.serve.scheduler`), and a request still QUEUED past its
    deadline finishes ``"cancelled"`` without ever taking a slot.  With
    ``preemption=True`` (default) a pending head that cannot be admitted
    — batch full, or its block reservation doesn't fit — evicts a
    running decode of a STRICTLY worse class: the victim's lane freezes,
    its committed blocks are registered under their prefix-chain keys
    and parked on the retention LRU, and the remainder requeues under
    the same uid as ``prompt ++ tokens`` with the leftover token budget
    — resuming later as a prefix-hit admission that recomputes only the
    partial last block.  Greedy resumed streams are bit-identical to the
    unpreempted replay (the repo-wide guarantee extends across
    preemption); the final :class:`Completion` merges all lives (full
    token stream, original ``prompt_len``, true ``first_token_at``,
    ``preemptions`` count).  Equal-priority traffic never preempts, so a
    priority-free workload is served exactly as before.  The optional
    ``prefill_budget_hook`` (see :class:`repro.serve.slo.SloBudgetAdapter`)
    is called at the top of every step and may retune
    ``prefill_chunk_budget`` against a TTFT SLO.
    """

    def __init__(self, model, cfg, *, batch: int, max_len: int,
                 max_prompt_len: int, max_stop_ids: int = 4,
                 cache_dtype=jnp.float32, seed: int = 0,
                 kv_layout: str = "paged", block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 decode_kernel: str = "reference",
                 prefill_kernel: str = "reference",
                 chunk_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 prefill_chunk_budget: Optional[int] = None,
                 prefix_reuse: bool = True,
                 prefix_retain_blocks: Optional[int] = None,
                 draft_model=None, spec_k: int = 0,
                 mesh=None,
                 preemption: bool = True, aging_every: int = 16,
                 prefill_budget_hook: Optional[
                     Callable[["ContinuousEngine"], Optional[int]]] = None):
        probe = getattr(model, "cache_kind", None)
        if probe is None:
            raise UnsupportedCacheError(
                f"{type(model).__name__} declares no serving cache kind; "
                "continuous batching needs per-slot state "
                "(cache_kind(cfg) capability probe)",
                roadmap_item="extend per-slot state to Whisper enc-dec "
                "caches (encoder K/V + cross-attention lanes)")
        self.cache_kind = probe(cfg)
        if self.cache_kind not in ("kv", "ring", "ssm", "hybrid"):
            raise UnsupportedCacheError(
                f"{type(model).__name__} reports unknown cache kind "
                f"{self.cache_kind!r}")
        if (draft_model is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH draft_model and spec_k >= 1 "
                "(or neither)")
        if spec_k < 0:
            raise ValueError("need spec_k >= 0")
        if draft_model is not None:
            dprobe = getattr(draft_model, "cache_kind", None)
            if (self.cache_kind != "kv" or dprobe is None
                    or dprobe(cfg) != "kv"):
                raise UnsupportedCacheError(
                    "speculative decoding requires the 'kv' cache kind for "
                    "both verifier and draft (multi-token verification needs "
                    "position-addressable KV lanes; ring/ssm/hybrid state "
                    "advances one token at a time)")
        self.spec_k = spec_k
        self.draft_model = draft_model
        if not 0 < max_prompt_len < max_len:
            raise ValueError("need 0 < max_prompt_len < max_len")
        if kv_layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if decode_kernel not in ("reference", "pallas"):
            raise ValueError(f"unknown decode_kernel {decode_kernel!r}")
        if decode_kernel == "pallas" and (kv_layout != "paged"
                                          or self.cache_kind != "kv"):
            raise ValueError(
                "decode_kernel='pallas' is the fused paged-attention "
                "kernel; it requires kv_layout='paged' (cache kind 'kv')")
        if prefill_kernel not in ("reference", "pallas"):
            raise ValueError(f"unknown prefill_kernel {prefill_kernel!r}")
        if prefill_kernel == "pallas" and self.cache_kind != "kv":
            # mirror the decode-kernel guard: the flash prefill-chunk
            # kernel streams a position-addressable KV prefix (paged pool
            # or dense lane); ring/ssm/hybrid per-slot state has neither
            raise UnsupportedCacheError(
                "prefill_kernel='pallas' is the flash prefill-chunk "
                "attention kernel; it requires position-addressable KV "
                "lanes (cache kind 'kv' — ring/ssm/hybrid state prefills "
                "through the reference path)",
                roadmap_item="make the kernels actually fast, and prove "
                "it compiled")
        if self.cache_kind != "kv":
            # ring / ssm / hybrid state cannot be paged or prefix-cached:
            # degrade gracefully to the per-slot layout (block reservation
            # skipped, prefix reuse auto-off)
            kv_layout = "dense"
        if chunk_size < 1:
            raise ValueError("need chunk_size >= 1")
        if buckets is None:
            # 2-3 compile widths: chunk_size plus halvings, so short prompts
            # and final partial chunks don't pay the full chunk pad
            buckets = sorted({max(1, chunk_size // 4),
                              max(1, chunk_size // 2), chunk_size})
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets or buckets[0] < 1:
            raise ValueError("buckets must be positive widths")
        if buckets[-1] < chunk_size:
            raise ValueError(
                f"largest bucket {buckets[-1]} < chunk_size {chunk_size}: "
                "a full chunk would not fit any compile width")
        self.chunk_size, self.buckets = chunk_size, buckets
        self.prefill_chunk_budget = (chunk_size if prefill_chunk_budget
                                     is None else prefill_chunk_budget)
        if self.prefill_chunk_budget < 1:
            raise ValueError("need prefill_chunk_budget >= 1")
        self.decode_kernel = decode_kernel
        self.prefill_kernel = prefill_kernel
        self.model, self.cfg = model, cfg
        self.batch, self.max_len = batch, max_len
        self.max_prompt_len, self.max_stop_ids = max_prompt_len, max_stop_ids
        self.kv_layout, self.cache_dtype = kv_layout, jnp.dtype(cache_dtype)
        if mesh is None:
            # a model committed to a multi-device mesh is served on it
            mesh = mesh_of(model)

        def alloc(make):
            """Per-slot state from ``make``; on a mesh each device
            allocates only its own shard of it."""
            if mesh is None:
                return make()
            sh = cache_shardings(jax.eval_shape(make), mesh)
            return jax.jit(make, out_shardings=sh)()

        if not hasattr(model, "prefill_chunk"):
            raise UnsupportedCacheError(
                f"{type(model).__name__} has no chunked-prefill path; "
                "continuous batching admits prompts chunk by chunk",
                roadmap_item="extend per-slot state to Whisper enc-dec "
                "caches (encoder K/V + cross-attention lanes)")
        if kv_layout == "paged":
            if block_size < 1:
                raise ValueError("need block_size >= 1")
            self.block_size = block_size
            self.n_blocks = (batch * (-(-max_len // block_size))
                             if n_blocks is None else n_blocks)
            if not hasattr(model, "init_paged_cache"):
                raise UnsupportedCacheError(
                    f"{type(model).__name__} has no paged KV cache; the "
                    "paged layout supports attention-KV models only")
            self.cache = alloc(lambda: model.init_paged_cache(
                batch, max_len, cfg, n_blocks=self.n_blocks,
                block_size=block_size, dtype=cache_dtype))
            retain = (self.n_blocks if prefix_retain_blocks is None
                      else prefix_retain_blocks)
            self.manager = PagedCacheManager(
                n_blocks=self.n_blocks, block_size=block_size, batch=batch,
                max_len=max_len, retain_blocks=retain if prefix_reuse else 0,
                prefix_reuse=prefix_reuse)
            self._table_dirty = False
            self._park_pos = self.manager.max_table * block_size
        else:
            try:
                self.cache = alloc(lambda: model.init_cache(
                    batch, max_len, cfg, dtype=cache_dtype, per_slot=True))
            except TypeError:
                raise UnsupportedCacheError(
                    f"{type(model).__name__} has no per-slot cache; "
                    "continuous batching needs independently advancing "
                    "slot state",
                    roadmap_item="extend per-slot state to Whisper "
                    "enc-dec caches (encoder K/V + cross-attention lanes)")
            self.manager = None
            self._park_pos = max_len
        if draft_model is not None:
            # the draft mirrors the verifier's cache layout; when paged it
            # shares the SAME block tables (one allocation drives both
            # pools), so reservation/refcount bookkeeping stays single
            if kv_layout == "paged":
                self.draft_cache = alloc(lambda: draft_model.init_paged_cache(
                    batch, max_len, cfg, n_blocks=self.n_blocks,
                    block_size=block_size, dtype=cache_dtype))
            else:
                self.draft_cache = alloc(lambda: draft_model.init_cache(
                    batch, max_len, cfg, dtype=cache_dtype, per_slot=True))
        else:
            self.draft_cache = None
        self.state = _SlotArrays(
            tok=jnp.zeros((batch,), jnp.int32),
            active=jnp.zeros((batch,), bool),
            temp=jnp.zeros((batch,), jnp.float32),
            n_gen=jnp.zeros((batch,), jnp.int32),
            max_new=jnp.ones((batch,), jnp.int32),
            stop_ids=jnp.full((batch, max_stop_ids), -1, jnp.int32),
        )
        self.mesh = mesh
        if mesh is not None:
            # Mesh-native placement, done ONCE at construction: params via
            # the Megatron specs (a no-op for weights that arrive placed
            # so, a device-to-device reshard otherwise: the model is never
            # gathered onto one device), caches by the paged/dense cache
            # rules as ``alloc`` made them (paged pool global over data,
            # kv heads over "model", block tables and slot batch over
            # "data"), slot state over "data".  The host-side allocator
            # (self.manager) stays global — block ids are placement-free;
            # only the device tables shard.  The jits below trace under
            # activation_mesh (the Pallas kernels then run shard-local)
            # and pin every returned cache/state leaf back to its
            # placement, so donation keeps layouts stable step over step.
            fsdp = global_config.fsdp_params
            model = self.model = jax.device_put(
                model, model_shardings(model, mesh, fsdp=fsdp))
            self._cache_sh = cache_shardings(self.cache, mesh)
            if self.draft_cache is not None:
                draft_model = self.draft_model = jax.device_put(
                    draft_model, model_shardings(draft_model, mesh,
                                                 fsdp=fsdp))
                self._draft_sh = cache_shardings(self.draft_cache, mesh)
            else:
                self._draft_sh = None
            self._state_sh = _SlotArrays(*(data_sharding(mesh, a.shape)
                                           for a in self.state))
            self.state = jax.device_put(self.state, self._state_sh)
        self.scheduler = Scheduler(batch, aging_every=aging_every)
        self.preemption = preemption
        self.prefill_budget_hook = prefill_budget_hook
        # uid -> earlier-lives state of a preempted request (tokens already
        # emitted, original prompt_len / first_token_at); merged into the
        # final Completion so clients see ONE request, not its lives
        self._resume_state: dict = {}
        self._preemptions = 0
        self._resumes = 0
        self._preempt_violations = 0  # lower-preempts-higher (must stay 0)
        # bind-time TTFT observations (seconds) for SLO adaptation hooks
        self.recent_ttfts: deque = deque(maxlen=256)
        self.hook_errors: deque = deque(maxlen=64)
        self._base_key = jax.random.PRNGKey(seed)
        self._tick = 0
        self._prefills: dict = {}  # slot -> _PrefillTask
        self._admit_seq = 0
        self._rr_seq = 0  # last admission seq served a chunk (rotation)
        self.on_token: Optional[Callable[[int, int], None]] = None
        # a raising on_token must not desync host/device state mid-step:
        # errors are recorded here (bounded) instead of propagating
        self.on_token_errors: deque = deque(maxlen=64)
        self._cancel_lock = threading.Lock()
        self._cancel_uids: set = set()  # uids to cancel at next step()
        self._step_events: list = []  # (uid, token) landed this step
        # prefill accounting (prefill_stats() / benchmarks); bounded like
        # scheduler.admitted so a long-lived server cannot leak step dicts
        self.step_log: deque = deque(maxlen=65536)
        # request waits (seconds) of each request's first life: submit to
        # admission, admission to first token
        self._queue_waits: deque = deque(maxlen=_WAIT_RESERVOIR)
        self._prefill_waits: deque = deque(maxlen=_WAIT_RESERVOIR)
        self._steps = 0
        self._decode_steps = 0
        self._mixed_steps = 0  # decode steps that also ran >= 1 chunk
        self._walk_entries = 0  # table entries the decode kernel walked
        self._table_entries = 0  # of batch x max_table a decode step
        self._programs_compiled = 0  # on the thread running step()
        self._step_compiles: list = []  # the phase of each, this step
        _listen_for_compiles()
        self._prompt_tokens_admitted = 0
        self._prefill_tokens_computed = 0  # true prompt tokens run
        self._prefill_tokens_padded = 0    # bucket widths run (compute cost)
        self._prefix_skipped_tokens = 0    # prompt tokens never recomputed
        self._prefill_chunks = 0
        self._max_step_prefill_tokens = 0
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0

        # the prefill-kernel kwarg rides along only when non-default: the
        # kv-kind guard above means every model that can see it accepts it,
        # and ring/ssm/hybrid families keep their original prefill_chunk
        # signature untouched
        pk_kw = ({} if prefill_kernel == "reference"
                 else {"prefill_kernel": prefill_kernel})

        # Every jitted role takes the weights as its first argument,
        # ``models = (model, draft_model)``: weights a jitted function
        # closes over are baked into its compiled program as constants —
        # one copy of the whole model per program on the device.  Each
        # role's body runs under a ``serve.*`` named scope, so its device
        # ops carry the role in their op path.
        def chunk_scope(need_logits):
            return "serve.chunk_last" if need_logits else "serve.chunk_mid"

        if draft_model is None:
            def chunk_fn(need_logits, models, toks, cache, slot, offset,
                         n_valid, dst=None):
                model = models[0]
                kw = {} if dst is None else {"dst": dst}
                with jax.named_scope(chunk_scope(need_logits)):
                    return model.prefill_chunk(toks, cache, slot=slot,
                                               offset=offset,
                                               n_valid=n_valid,
                                               need_logits=need_logits,
                                               **pk_kw, **kw)
        else:
            # the draft prefills the same chunk into its own cache (logits
            # never needed — the verifier's final chunk seeds the first
            # sample; the draft only ever decodes)
            def chunk_fn(need_logits, models, toks, cache, dcache, slot,
                         offset, n_valid, dst=None):
                model, draft_model = models
                kw = {} if dst is None else {"dst": dst}
                with jax.named_scope(chunk_scope(need_logits)):
                    logits, cache = model.prefill_chunk(
                        toks, cache, slot=slot, offset=offset,
                        n_valid=n_valid, need_logits=need_logits, **pk_kw,
                        **kw)
                    _, dcache = draft_model.prefill_chunk(
                        toks, dcache, slot=slot, offset=offset,
                        n_valid=n_valid, need_logits=False, **pk_kw, **kw)
                return logits, cache, dcache

        @jax.named_scope("serve.bind")
        def bind_fn(state, slot, logits, length, temp, max_new, stop_row,
                    key):
            first = sample_tokens(logits, temp[None], key)[0]
            done0 = (jnp.any(first == stop_row) | (max_new <= 1)
                     | (length >= max_len))
            state = state._replace(
                tok=state.tok.at[slot].set(first),
                active=state.active.at[slot].set(~done0),
                temp=state.temp.at[slot].set(temp),
                n_gen=state.n_gen.at[slot].set(1),
                max_new=state.max_new.at[slot].set(max_new),
                stop_ids=state.stop_ids.at[slot].set(stop_row),
            )
            return state, first, done0

        if self.manager is not None:
            # paged decode takes the kernel knob; dense/per-slot model
            # families keep their original decode signature
            dk = self.decode_kernel

            def model_decode(m, tok, cache):
                return m.decode(tok, cache, decode_kernel=dk)
        else:
            def model_decode(m, tok, cache):
                return m.decode(tok, cache)

        stateful = self.cache_kind != "kv"

        @jax.named_scope("serve.decode")
        def decode_fn(models, cache, state, key):
            logits, new_cache = model_decode(models[0], state.tok[:, None],
                                             cache)
            nxt = sample_tokens(logits[:, 0], state.temp, key)
            nxt = jnp.where(state.active, nxt, state.tok)
            if stateful:
                # ring / recurrent state has no out-of-range park row the
                # scatter could drop into: freeze inactive slots (finished
                # or mid-chunked-prefill) by a slot-wise select over the
                # whole cache — every leaf carries the slot axis at dim 1
                act = state.active
                new_cache = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        act.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
                    new_cache, cache)
                length = new_cache.length
            else:
                # frozen slots keep their cache position and token
                length = jnp.where(state.active[None, :], new_cache.length,
                                   cache.length)
                new_cache = new_cache._replace(length=length)
            n_gen = jnp.where(state.active, state.n_gen + 1, state.n_gen)
            stop_hit = jnp.any(nxt[:, None] == state.stop_ids, axis=-1)
            done = state.active & (stop_hit | (n_gen >= state.max_new)
                                   | (length[0] >= max_len))
            state = state._replace(tok=nxt, active=state.active & ~done,
                                   n_gen=n_gen)
            return new_cache, state, nxt, done

        @jax.named_scope("serve.spec_draft")
        def spec_draft_fn(models, dcache, vlen, state):
            """Draft ``spec_k`` greedy tokens per slot with the factorized
            model (cheap single-token steps).  The draft frontier is synced
            from the VERIFIER's length ``vlen`` at entry — the verifier's
            counter is the single source of truth for committed positions,
            so the draft cache needs no bookkeeping of its own (and the two
            caches never share a length buffer, which donation forbids).
            Inactive slots run parked: their writes drop and their drafted
            tokens are frozen to ``state.tok``."""
            dcache = dcache._replace(length=vlen)

            def body(carry, _):
                tok, dc = carry
                logits, dc = model_decode(models[1], tok[:, None], dc)
                nxt = greedy_tokens(logits[:, 0])
                nxt = jnp.where(state.active, nxt, tok)
                return (nxt, dc), nxt

            (_, dcache), drafts = jax.lax.scan(
                body, (state.tok, dcache), None, length=spec_k)
            return dcache, drafts.T  # (B, k)

        @jax.named_scope("serve.spec_verify")
        def spec_verify_fn(models, cache, state, drafts):
            """Verify ``spec_k`` drafted tokens in ONE dense multi-token
            decode and commit the agreeing prefix + one correction token.

            Inputs ``X = [tok, d_1 .. d_{k-1}]`` decode at positions
            ``pos0 .. pos0+k-1``; ``g_j = argmax`` of the dense logits at
            position ``pos0+j`` is what sequential greedy would emit after
            ``X_0..X_j``, so drafts verify via ``d_{j+1} == g_j`` and the
            emitted tokens are ALWAYS ``g_0..g_{m-1}`` — dense argmaxes
            conditioned on accepted context, bit-exact to plain greedy no
            matter what the draft produced.  The frontier lands at
            ``pos0 + m``; rows past that hold unaccepted writes the next
            round rewrites before any query can attend them."""
            k = spec_k
            pos0 = cache.length[0]  # (B,) pre-decode frontier, all layers ==
            inp = jnp.concatenate([state.tok[:, None], drafts[:, :-1]],
                                  axis=1)
            logits, cache = model_decode(models[0], inp, cache)
            g = greedy_tokens(logits)  # (B, k)
            lead = jnp.cumprod((drafts == g).astype(jnp.int32), axis=1)
            n_match = lead.sum(axis=1)  # leading drafts that verified
            m0 = jnp.minimum(n_match + 1, k)  # + one correction token
            j = jnp.arange(k)
            # per-token stop conditions, mirroring decode_fn's done logic
            stop_hit = jnp.any(g[:, :, None] == state.stop_ids[:, None, :],
                               axis=-1)
            done_at = (stop_hit
                       | (state.n_gen[:, None] + j[None, :] + 1
                          >= state.max_new[:, None])
                       | (pos0[:, None] + j[None, :] + 1 >= max_len))
            d32 = done_at.astype(jnp.int32)
            prior_done = jnp.cumsum(d32, axis=1) - d32
            emit = ((j[None, :] < m0[:, None]) & (prior_done == 0)
                    & state.active[:, None])
            m = emit.sum(axis=1)  # (B,) tokens actually emitted
            done = jnp.any(done_at & emit, axis=1)
            new_tok = jnp.take_along_axis(
                g, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            new_tok = jnp.where(state.active, new_tok, state.tok)
            # frontier = pos0 + m for live slots; parked/frozen slots get
            # their pre-decode value back (the multi-token step advanced
            # every row's counter by k)
            new_len = jnp.broadcast_to(
                jnp.where(state.active, pos0 + m, pos0)[None, :],
                cache.length.shape)
            cache = cache._replace(length=new_len)
            n_gen = state.n_gen + jnp.where(state.active, m, 0)
            n_acc = jnp.where(state.active, jnp.minimum(n_match, m), 0)
            state = state._replace(tok=new_tok,
                                   active=state.active & ~done, n_gen=n_gen)
            return cache, state, g, m, n_acc, done

        if mesh is not None:
            # Wrap every jitted body: the trace runs inside activation_mesh
            # (the ContextVar is read at TRACE time, so the scope rides
            # into the compiled step no matter which thread later calls
            # it), and the returned cache/state trees are pinned to their
            # construction-time shardings — donated buffers then round-trip
            # with identical layouts and the `.sharding` of self.cache
            # stays the intended NamedSharding forever.
            cache_sh, draft_sh = self._cache_sh, self._draft_sh
            state_sh = self._state_sh

            def _pin(tree, sh):
                return jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, tree, sh)

            inner_chunk = chunk_fn
            if draft_model is None:
                def chunk_fn(need_logits, models, toks, cache, *rest):
                    with activation_mesh(mesh):
                        logits, c = inner_chunk(need_logits, models, toks,
                                                cache, *rest)
                    return logits, _pin(c, cache_sh)
            else:
                def chunk_fn(need_logits, models, toks, cache, dcache,
                             *rest):
                    with activation_mesh(mesh):
                        logits, c, dc = inner_chunk(need_logits, models,
                                                    toks, cache, dcache,
                                                    *rest)
                    return logits, _pin(c, cache_sh), _pin(dc, draft_sh)

            inner_bind = bind_fn

            def bind_fn(state, *rest):
                st, first, done0 = inner_bind(state, *rest)
                return _pin(st, state_sh), first, done0

            inner_decode = decode_fn

            def decode_fn(models, cache, state, key):
                with activation_mesh(mesh):
                    c, st, nxt, done = inner_decode(models, cache, state,
                                                    key)
                return _pin(c, cache_sh), _pin(st, state_sh), nxt, done

            if draft_model is not None:
                inner_spec_draft = spec_draft_fn

                def spec_draft_fn(models, dcache, vlen, state):
                    with activation_mesh(mesh):
                        dc, drafts = inner_spec_draft(models, dcache, vlen,
                                                      state)
                    return _pin(dc, draft_sh), drafts

                inner_spec_verify = spec_verify_fn

                def spec_verify_fn(models, cache, state, drafts):
                    with activation_mesh(mesh):
                        c, st, g, m, n_acc, done = inner_spec_verify(
                            models, cache, state, drafts)
                    return (_pin(c, cache_sh), _pin(st, state_sh), g, m,
                            n_acc, done)

        # ONE jit per role; the chunk jits specialize per bucket width (the
        # buckets bound how many widths ever occur).  Mid-prompt chunks use
        # the logits-free variant — only a prompt's FINAL chunk pays the
        # final-norm + vocab-projection matmul
        self._models = (model, draft_model)
        chunk_donate = (2,) if draft_model is None else (2, 3)
        self._chunk_last = jax.jit(
            lambda *a: chunk_fn(True, *a), donate_argnums=chunk_donate)
        self._chunk_mid = jax.jit(
            lambda *a: chunk_fn(False, *a), donate_argnums=chunk_donate)
        self._bind = jax.jit(bind_fn, donate_argnums=(0,))
        self._decode = jax.jit(decode_fn, donate_argnums=(1, 2))
        if draft_model is not None:
            self._spec_draft = jax.jit(spec_draft_fn, donate_argnums=(1,))
            self._spec_verify = jax.jit(spec_verify_fn,
                                        donate_argnums=(1, 2))

    # -- request intake ------------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               stop_ids: Sequence[int] = (), priority: int = 1,
               timeout_s: Optional[float] = None) -> int:
        """Queue one request; returns its uid (priority-class admission,
        FIFO within a class — see :class:`repro.serve.scheduler`).

        ``prompt`` is either a token-id sequence (with ``max_new_tokens``
        etc. given here) or a prebuilt :class:`Request` — both go through
        the same engine-limit validation.  ``priority`` is the admission
        class (0 = most urgent, default 1); ``timeout_s`` a deadline the
        engine enforces while the request is still QUEUED (a request that
        cannot start in time finishes ``"cancelled"``)."""
        if isinstance(prompt, Request):
            req = prompt
        else:
            if max_new_tokens is None:
                raise ValueError("max_new_tokens is required")
            req = Request(prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=max_new_tokens,
                          temperature=temperature, stop_ids=tuple(stop_ids),
                          priority=priority, timeout_s=timeout_s)
        if req.prompt.size > self.max_prompt_len:
            raise ValueError(
                f"prompt length {req.prompt.size} > max_prompt_len "
                f"{self.max_prompt_len}")
        if len(req.stop_ids) > self.max_stop_ids:
            raise ValueError(f"more than {self.max_stop_ids} stop ids")
        if self.spec_k and req.temperature != 0.0:
            raise ValueError(
                "speculative decoding is greedy-only: the accepted-prefix "
                "argument needs deterministic argmax on both models "
                "(temperature must be 0)")
        if self.manager is not None:
            need = self.manager.blocks_needed(self._total_tokens(req))
            if need > self.n_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool has only "
                    f"{self.n_blocks}; raise n_blocks or lower "
                    "max_new_tokens")
        return self.scheduler.submit(req)

    def _total_tokens(self, req: Request) -> int:
        """Worst-case cache positions a request can occupy (reservation)."""
        return min(int(req.prompt.size) + int(req.max_new_tokens),
                   self.max_len)

    def _next_key(self) -> jax.Array:
        self._tick += 1
        return jax.random.fold_in(self._base_key, self._tick)

    # -- serving loop --------------------------------------------------------

    def _next_admission(self):
        """Priority-class head-of-line admission (FIFO within a class);
        the paged layout additionally gates on the chosen head's block
        reservation fitting the free pool."""
        if self.manager is None:
            return self.scheduler.next_admission()
        return self.scheduler.next_admission(
            admissible=lambda r: self.manager.can_admit(
                r.prompt, self._total_tokens(r)))

    def _finish(self, slot: int, cache_pos: int,
                reason: Optional[str] = None) -> Completion:
        """Evict a finished slot: classify, release its KV blocks (paged),
        and hand the slot back to the scheduler.  ``reason`` overrides the
        classifier (cancellation — a cancelled request must never be
        reported as a natural ``length``/``stop`` finish, even when the
        cancel lands on the same step its limit would have)."""
        if reason is None:
            reason = self.scheduler.finish_reason(slot, cache_pos,
                                                  self.max_len)
        self._release_slot(slot)
        return self.scheduler.finish(slot, reason)

    def _release_slot(self, slot: int) -> None:
        """Return a slot's paged blocks and rewind any dependents its
        orphaned (registered-but-unwritten) prefix blocks would strand."""
        if self.manager is not None:
            orphans = self.manager.release(slot)
            self._table_dirty = True
            if orphans:
                self._rewind_dependents(orphans)

    def _flush_table(self) -> None:
        if self.manager is None or not self._table_dirty:
            return
        with _span("serve.table_flush"):
            # upload a copy: a transfer may read its host buffer after the
            # call returns, and the manager edits its mirror in place
            tables = self.manager.tables.copy()
            self.cache = self.cache._replace(
                table=self._put_table(tables, draft=False))
            if self.draft_cache is not None:
                # materialized separately on purpose: the two caches must
                # never share a device buffer (both are donated to jits)
                self.draft_cache = self.draft_cache._replace(
                    table=self._put_table(tables, draft=True))
            self._table_dirty = False

    def _put_table(self, tables: np.ndarray, *, draft: bool) -> jax.Array:
        """Upload the host block tables; on a mesh the batch dim lands
        sharded over "data" so each data shard only holds its slots'
        rows."""
        if self.mesh is None:
            return jnp.asarray(tables)
        sh = (self._draft_sh if draft else self._cache_sh).table
        return jax.device_put(tables, sh)

    # -- cancellation --------------------------------------------------------

    def cancel(self, uid: int) -> bool:
        """Request cancellation of a submitted request.

        Thread-safe: may be called from any thread while ``step()`` runs
        (the HTTP front door calls it from the event loop on client
        disconnect and deadline expiry).  The cancel takes effect at the
        START of the next ``step()``, which returns the request's
        ``finish_reason="cancelled"`` :class:`Completion` alongside any
        natural finishes — a pending request leaves the queue, a
        prefilling or running request releases its slot, parked write
        frontier, and paged blocks.  Returns whether the uid *looked*
        live at call time (best-effort — the request may finish naturally
        before the cancel drains, in which case the cancel is a no-op);
        cancelling an unknown or finished uid is harmless."""
        with self._cancel_lock:
            self._cancel_uids.add(uid)
        try:
            state, _ = self.scheduler.find(uid)
        except RuntimeError:  # scheduler deques mutating under step()
            return True
        return state is not None

    def _drain_cancels(self) -> list:
        """Apply every cancel() recorded since the last step (host-order
        deterministic: sorted by uid)."""
        with self._cancel_lock:
            if not self._cancel_uids:
                return []
            uids, self._cancel_uids = self._cancel_uids, set()
        out = []
        for uid in sorted(uids):
            comp = self._cancel_now(uid)
            if comp is not None:
                out.append(comp)
        return out

    def _cancel_now(self, uid: int) -> Optional[Completion]:
        state, slot = self.scheduler.find(uid)
        if state == "pending":
            return self.scheduler.cancel_pending(uid)
        if state == "prefilling":
            # drop the host task; the slot's write frontier is already
            # parked out of range (since _begin_prefill), so no decode
            # write can land anywhere — just return the blocks
            del self._prefills[slot]
            self._release_slot(slot)
            return self.scheduler.cancel_prefilling(slot)
        if state == "running":
            # freeze the lane exactly like a natural in-graph finish
            # (inactive slots' tokens/positions stop advancing; paged
            # writes drop into the sentinel row once the table clears),
            # then evict with the explicit reason
            self.state = self.state._replace(
                active=self.state.active.at[slot].set(False))
            pos = int(np.asarray(self.cache.length)[0, slot])
            return self._finish(slot, pos, reason="cancelled")
        return None  # unknown uid or already finished: no-op

    def _rewind_dependents(self, orphans: Tuple[int, ...]) -> None:
        """Un-strand prefills whose prefix-hit chain includes ``orphans``
        — blocks a cancelled provider registered but never wrote.  Such a
        task would wait in ``blocks_ready`` forever; instead its hit
        boundary is rewound to the first orphan in its chain and it
        recomputes the tail of the prefix itself — writing the SAME bytes
        (the sha256 chain matched, so the tokens match and prefill is
        deterministic) and publishing the blocks for anyone behind it.

        Safe by construction: ``blocks_ready`` gates all-or-nothing, so a
        task with ANY unpublished hit block has run zero chunks — nothing
        was computed from the orphaned content, and ``consumed`` still
        sits at the admission-time skip point.  Writing a shared pending
        block here is the one sanctioned exception to the shared-blocks-
        are-immutable rule: every reader is gated until publish, and the
        rewritten content is bit-identical."""
        orphans = set(orphans)
        for task in self._prefills.values():
            idx = next((i for i, b in enumerate(task.hit_bids)
                        if b in orphans), None)
            if idx is None:
                continue
            assert task.chunks == 0, "rewind of a started prefill"
            new_cached = idx * self.block_size
            new_start = min(new_cached, task.plen - 1)
            # give back the skip accounting the rewound span claimed
            self._prefix_skipped_tokens -= task.consumed - new_start
            self.manager.prefix_hit_tokens -= task.cached - new_cached
            task.cached = new_cached
            task.consumed = new_start
            task.hit_bids = task.hit_bids[:idx]

    # -- preemption ----------------------------------------------------------

    def _maybe_preempt(self) -> None:
        """Evict running decodes so a blocked higher-priority pending head
        can start.  A victim's priority must be STRICTLY worse (larger)
        than the head's — equal-priority traffic never preempts, so a
        priority-free workload behaves exactly as before.  Victims are
        the worst-priority running slots, youngest first; prefilling
        slots are never preempted (their compute is the very thing
        preemption tries to reallocate).  Bounded by the batch size per
        step."""
        sched = self.scheduler
        for _ in range(self.batch):
            head = sched.peek_next()
            if head is None:
                return
            fits = (self.manager is None
                    or self.manager.can_admit(head.prompt,
                                              self._total_tokens(head)))
            if sched.free_slot() is not None and fits:
                return  # head is admissible as-is
            victims = [(s.request.priority, s.request.uid, slot)
                       for slot, s in enumerate(sched.slots)
                       if s is not None
                       and s.request.priority > head.priority]
            if not victims:
                return
            prio, _, slot = max(victims)
            if prio <= head.priority:
                # unreachable by construction (the filter above is strict);
                # counted defensively — the loadgen --strict gate and the
                # /metrics scrape assert this stays 0
                self._preempt_violations += 1
                return
            self._preempt_slot(slot)

    def _preempt_slot(self, slot: int) -> None:
        """Park a running decode and requeue its remainder.

        The victim's lane freezes exactly like a cancel; its cache holds
        ``prompt ++ tokens[:-1]`` (the last sampled token was still
        waiting in ``state.tok`` for the next decode).  On the paged
        layout those committed full blocks are registered under their
        chain keys and parked on the retention LRU at release, so the
        resume — a re-submission of ``prompt ++ tokens`` with the
        remaining token budget, under the SAME uid — comes back as a
        prefix hit that recomputes only the partial last block.  Greedy
        decoding is deterministic, so the resumed stream is bit-identical
        to the unpreempted replay; a sampled (temperature > 0) request
        resumes with fresh randomness."""
        self.state = self.state._replace(
            active=self.state.active.at[slot].set(False))
        pos = int(np.asarray(self.cache.length)[0, slot])
        req, tokens, first_at = self.scheduler.preempt(slot)
        k = len(tokens)
        assert pos == req.prompt.size + k - 1, \
            f"preempt pos {pos} != plen {req.prompt.size} + {k} - 1"
        if self.manager is not None:
            committed = np.concatenate(
                [req.prompt, np.asarray(tokens[:-1], np.int32)])
            self.manager.register_chain(slot, committed)
        self._release_slot(slot)
        prior = self._resume_state.get(req.uid)
        self._resume_state[req.uid] = {
            "tokens": (prior["tokens"] if prior else []) + list(tokens),
            "prompt_len": (prior["prompt_len"] if prior
                           else int(req.prompt.size)),
            "first_token_at": (prior["first_token_at"] if prior
                               else first_at),
            "preemptions": (prior["preemptions"] if prior else 0) + 1,
        }
        resume = dataclasses.replace(
            req,
            prompt=np.concatenate([req.prompt,
                                   np.asarray(tokens, np.int32)]),
            max_new_tokens=req.max_new_tokens - k)
        self.scheduler.requeue(resume)
        self._preemptions += 1

    def _merge_resume(self, comp: Completion) -> Completion:
        """Fold a resumed request's earlier lives into its final
        Completion: the client sees the original prompt_len, the full
        token stream, the true first-token time, and how many times the
        request was preempted along the way."""
        st = self._resume_state.pop(comp.uid, None)
        if st is None:
            return comp
        comp.tokens = st["tokens"] + comp.tokens
        comp.prompt_len = st["prompt_len"]
        comp.first_token_at = st["first_token_at"]
        comp.preemptions = st["preemptions"]
        return comp

    def _emit(self, uid: int, token: int) -> None:
        self._step_events.append((uid, int(token)))
        if self.on_token is not None:
            try:
                self.on_token(uid, int(token))
            except Exception as exc:
                # a consumer bug must not desync host bookkeeping from
                # device state (leaked slots/blocks, missing step_log):
                # record and keep stepping
                self.on_token_errors.append((uid, int(token), repr(exc)))

    def _bucket_width(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _begin_prefill(self, slot: int, req: Request) -> None:
        """Reserve the slot (and, paged, its blocks) for a request; chunks
        run later under the step budget."""
        plen = int(req.prompt.size)
        if self.manager is not None:
            cached, hit_bids = self.manager.admit(slot, req.prompt,
                                                  self._total_tokens(req))
            self._table_dirty = True
        else:
            cached, hit_bids = 0, ()
        # start AFTER the resident prefix — but always recompute at least
        # the final token: its logits seed the first sample
        start = min(cached, plen - 1)
        self._admit_seq += 1
        self._prefills[slot] = _PrefillTask(
            req=req, slot=slot, seq=self._admit_seq, plen=plen,
            cached=cached, consumed=start, hit_bids=hit_bids)
        self.scheduler.begin_prefill(slot, req)
        if req.uid not in self._resume_state:  # a resume is no new wait
            self._queue_waits.append(req.admitted_at - req.submitted_at)
        self._prompt_tokens_admitted += plen
        self._prefix_skipped_tokens += start
        # park the slot's write frontier out of range: the batched decode
        # step still scatters a K/V row for every slot, and a prefilling
        # slot's stale position could point anywhere — including, in the
        # paged layout, INSIDE a shared prefix block it just mapped
        self.cache = self.cache._replace(
            length=self.cache.length.at[:, slot].set(self._park_pos))
        if self.draft_cache is not None:
            self.draft_cache = self.draft_cache._replace(
                length=self.draft_cache.length.at[:, slot].set(
                    self._park_pos))

    def _chunk_extent(self, task: _PrefillTask) -> Tuple[int, int]:
        """(true length, padded bucket width) of the task's next chunk —
        the ONE sizing formula both the budget check and the chunk run
        consult."""
        l = min(self.chunk_size, task.plen - task.consumed)
        return l, self._bucket_width(l)

    def _run_chunk(self, task: _PrefillTask, l: int, w: int) -> int:
        """Feed one bucket-padded chunk of extent ``(l, w)`` (from
        :meth:`_chunk_extent`); returns the padded width spent."""
        with _span("serve.prefill_chunk"):
            toks = np.zeros((1, w), np.int32)
            toks[0, :l] = task.req.prompt[task.consumed:task.consumed + l]
            final = task.consumed + l >= task.plen
            run = self._chunk_last if final else self._chunk_mid
            caches = ((self.cache,) if self.draft_cache is None
                      else (self.cache, self.draft_cache))
            args = (self._models, self._put_host(toks), *caches,
                    jnp.asarray(task.slot, jnp.int32),
                    jnp.asarray(task.consumed, jnp.int32),
                    jnp.asarray(l, jnp.int32))
            if self.manager is not None:
                dst = self.manager.scatter_rows(task.slot, task.consumed, w,
                                                lo=task.cached, hi=task.plen)
                out = run(*args, self._put_host(dst))
            else:
                out = run(*args)
            if self.draft_cache is None:
                logits, self.cache = out
            else:
                logits, self.cache, self.draft_cache = out
            if final:
                task.logits = logits
            task.consumed += l
            task.chunks += 1
            if self.manager is not None:
                self.manager.publish(task.slot, task.consumed)
            self._prefill_tokens_computed += l
            self._prefill_tokens_padded += w
            self._prefill_chunks += 1
        return w

    def _put_host(self, arr) -> jax.Array:
        """Upload one admitted host array.  On a mesh this commits the
        chunk onto the data axis (a single prompt's chunk has batch 1, so
        the placement resolves to replication across the data shards);
        off-mesh it is a plain transfer."""
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(np.asarray(arr),
                              data_sharding(self.mesh, np.shape(arr)))

    def _complete_prefill(self, task: _PrefillTask) -> list:
        """Sample the first token from the final chunk's logits and move
        the slot into the decode batch (possibly finishing immediately)."""
        with _span("serve.bind"):
            req = task.req
            stop_row = np.full((self.max_stop_ids,), -1, np.int32)
            stop_row[:len(req.stop_ids)] = req.stop_ids
            self.state, first, done0 = self._bind(
                self.state, jnp.asarray(task.slot, jnp.int32), task.logits,
                jnp.asarray(task.plen, jnp.int32),
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.max_new_tokens, jnp.int32),
                jnp.asarray(stop_row), self._next_key())
            del self._prefills[task.slot]
            self.scheduler.bind(task.slot, req, int(first))
            if req.uid in self._resume_state:
                self._resumes += 1  # resumed life: not a real TTFT
            else:
                first_at = self.scheduler.slots[task.slot].first_token_at
                self.recent_ttfts.append(first_at - req.submitted_at)
                self._prefill_waits.append(first_at - req.admitted_at)
            self._emit(req.uid, int(first))
            if bool(done0):
                return [self._finish(task.slot, task.plen)]
            return []

    def _advance_prefills(self) -> Tuple[list, int]:
        """Run chunks round-robin under the step budget, ROTATING the
        starting task across steps: the service order picks up after the
        last task that got a chunk, so when the per-step budget only
        covers one chunk, a short prompt admitted behind a long one still
        gets its turn on the next step instead of waiting out the long
        prompt's whole prefill (the head-of-line stall chunking exists to
        remove).  Always makes progress when any prefill is runnable — a
        budget smaller than the smallest bucket still advances one chunk
        per step.  A task whose prefix-hit blocks are still being written
        by an earlier prefill is skipped until they publish."""
        finished: list = []
        spent = 0
        progressed = True
        while self._prefills and progressed:
            progressed = False
            tasks = sorted(self._prefills.values(), key=lambda t: t.seq)
            pivot = next((i for i, t in enumerate(tasks)
                          if t.seq > self._rr_seq), 0)
            for task in tasks[pivot:] + tasks[:pivot]:
                if self.manager is not None and not \
                        self.manager.blocks_ready(task.hit_bids):
                    continue
                l, w = self._chunk_extent(task)
                if spent and spent + w > self.prefill_chunk_budget:
                    return finished, spent
                spent += self._run_chunk(task, l, w)
                self._rr_seq = task.seq
                progressed = True
                if task.consumed >= task.plen:
                    finished.extend(self._complete_prefill(task))
        return finished, spent

    def step(self) -> list:
        """One scheduling round: apply cancels, admit, chunk prefills
        under the budget, bind finished prefills, then one batched decode
        step.  Returns the :class:`Completion`s finished this step
        (cancelled ones included).  Each phase runs under its ``serve.*``
        host span (see ``repro/serve/README.md``)."""
        t0 = time.monotonic()
        self._step_events = []
        self._step_compiles = []
        chunks0 = self._prefill_chunks
        with _span("serve.step", engine=self):
            if self.prefill_budget_hook is not None:
                try:
                    budget = self.prefill_budget_hook(self)
                    if budget is not None:
                        self.prefill_chunk_budget = max(1, int(budget))
                except Exception as exc:
                    # an operator hook bug must not take serving down
                    self.hook_errors.append(repr(exc))
            with _span("serve.admit"):
                finished = self._drain_cancels()
                finished.extend(self.scheduler.expire_pending())
                if self.preemption:
                    self._maybe_preempt()
                while (adm := self._next_admission()) is not None:
                    self._begin_prefill(*adm)
            prefill_spent = 0
            if self._prefills:
                self._flush_table()
                done, prefill_spent = self._advance_prefills()
                finished.extend(done)
                self._max_step_prefill_tokens = max(
                    self._max_step_prefill_tokens, prefill_spent)

            running = self.scheduler.running_slots()
            if running and self.spec_k:
                self._flush_table()
                with _span("serve.decode"):
                    self.draft_cache, drafts = self._spec_draft(
                        self._models, self.draft_cache, self.cache.length,
                        self.state)
                    self.cache, self.state, g, m, n_acc, done = \
                        self._spec_verify(self._models, self.cache,
                                          self.state, drafts)
                with _span("serve.host_sync"):
                    g_np, m_np = np.asarray(g), np.asarray(m)
                    done_np = np.asarray(done)
                    pos_np = np.asarray(self.cache.length[0])
                    accepted = int(np.asarray(n_acc).sum())
                self._spec_rounds += 1
                self._spec_drafted += self.spec_k * len(running)
                self._spec_accepted += accepted
                with _span("serve.emit"):
                    for slot in running:
                        uid = self.scheduler.slots[slot].request.uid
                        for tok in g_np[slot, :m_np[slot]]:
                            self.scheduler.append_token(slot, tok)
                            self._emit(uid, tok)
                        if done_np[slot]:
                            finished.append(
                                self._finish(slot, int(pos_np[slot])))
            elif running:
                self._flush_table()
                with _span("serve.decode"):
                    self.cache, self.state, nxt, done = self._decode(
                        self._models, self.cache, self.state,
                        self._next_key())
                with _span("serve.host_sync"):
                    nxt_np, done_np = np.asarray(nxt), np.asarray(done)
                    pos_np = np.asarray(self.cache.length[0])
                    if self.manager is not None:
                        self._count_walk(pos_np, running)
                with _span("serve.emit"):
                    for slot in running:
                        self.scheduler.append_token(slot, nxt_np[slot])
                        self._emit(self.scheduler.slots[slot].request.uid,
                                   nxt_np[slot])
                        if done_np[slot]:
                            finished.append(
                                self._finish(slot, int(pos_np[slot])))
            chunks = self._prefill_chunks - chunks0
            self._steps += 1
            if running:
                self._decode_steps += 1
                if chunks:
                    self._mixed_steps += 1
            self.step_log.append({
                "t0": t0,
                "wall_s": time.monotonic() - t0,
                "prefill_tokens": prefill_spent,
                "chunks": chunks,
                "decoded_slots": len(running),
                "compiles": self._step_compiles,
            })
            return [self._merge_resume(c) for c in finished]

    def _count_walk(self, pos_np: np.ndarray, running) -> None:
        """Add one decode step's paged-kernel walk to the
        ``decode_walk_share`` counter, from the host's table mirror and
        the positions just read: the running slots advanced by one, so
        the kernel saw each one position earlier."""
        # imported on first use, as the decode path imports the kernel:
        # importing the engine loads no Pallas module
        from repro.kernels.paged_attention import walk_bound

        pos = pos_np.copy()
        pos[running] -= 1
        tables = self.manager.tables
        self._walk_entries += int(walk_bound(
            tables, pos, self.block_size, self.manager.sentinel).sum())
        self._table_entries += tables.size

    # -- introspection -------------------------------------------------------

    @property
    def step_events(self) -> Tuple[Tuple[int, int], ...]:
        """``(uid, token)`` pairs emitted by the most recent ``step()`` —
        the pull half of streaming for drivers that call ``step()``
        directly (the HTTP pump) instead of iterating ``stream()``."""
        return tuple(self._step_events)

    def kv_stats(self) -> dict:
        """HBM accounting for the KV cache (bytes, both layouts).

        ``kv_allocated_bytes`` is what the layout reserves up front;
        ``kv_peak_resident_bytes`` is the high-water mark of bytes holding
        live tokens — for the dense layout the two coincide (every slot
        pins a ``max_len`` lane), for the paged layout the peak tracks
        blocks actually in use, which is what a right-sized pool would
        need.  Parked (LRU-retained) prefix blocks are reclaimable warm
        capacity and excluded from the in-use numbers.  For the stateful
        kinds (ring / ssm / hybrid) the accounting covers every state
        leaf (KV lanes + conv/ssm buffers), and ``kv_lane_tokens``
        reports the per-slot lane length — ``window`` for ring lanes (the
        O(window)-not-O(max_len) bound the benchmark asserts), absent for
        pure-SSM state.

        With speculative decoding on, the draft model's mirror cache is
        real HBM too: ``draft_kv_allocated_bytes`` splits it out and
        every aggregate number includes it.  In the paged layout the
        draft shares the verifier's block tables, so one block 'in use'
        pins rows in BOTH pools — per-block bytes cover the two pools
        together.

        ``decode_walk_share`` (paged) is how much of the block tables the
        decode kernel walks: over the plain decode steps since
        :meth:`reset_stats`, the sum of each slot's walk bound
        (:func:`repro.kernels.paged_attention.walk_bound`) over the sum
        of ``batch x max_table``; 0.0 before the first decode step.

        ``model_shards`` is the size of the mesh's "model" axis (1 off a
        mesh), and ``pool_bytes_per_device`` the KV state (draft mirror
        included) that one device holds: ``kv_allocated_bytes`` over the
        model shards where the kv heads divide them."""

        def _leaf_bytes(cache, per_device=False):
            return sum(math.prod(a.sharding.shard_shape(a.shape)
                                 if per_device else a.shape)
                       * a.dtype.itemsize
                       for f, a in zip(cache._fields, cache)
                       if f not in ("length", "table"))

        shards = {"model_shards": (1 if self.mesh is None
                                   else self.mesh.shape.get("model", 1)),
                  "pool_bytes_per_device": sum(
                      _leaf_bytes(c, per_device=True)
                      for c in (self.cache, self.draft_cache)
                      if c is not None)}

        if self.manager is None:
            leaves = {f: a for f, a in zip(self.cache._fields, self.cache)
                      if f not in ("length", "table")}
            alloc = sum(a.size * a.dtype.itemsize for a in leaves.values())
            stats = {"kv_layout": self.kv_layout,
                     "cache_kind": self.cache_kind,
                     "kv_allocated_bytes": alloc,
                     "kv_peak_resident_bytes": alloc}
            if self.draft_cache is not None:
                dalloc = _leaf_bytes(self.draft_cache)
                stats["draft_kv_allocated_bytes"] = dalloc
                stats["kv_allocated_bytes"] += dalloc
                stats["kv_peak_resident_bytes"] += dalloc
            if "k" in leaves:  # per-slot KV lanes (dense or ring)
                k = leaves["k"]
                stats["kv_lane_tokens"] = k.shape[2]
                if self.cache_kind in ("ring", "hybrid"):
                    stats["kv_ring_bytes"] = 2 * k.size * k.dtype.itemsize
            return {**stats, **shards}
        alloc = 2 * self.cache.k.size * self.cache.k.dtype.itemsize
        block_bytes = 2 * (self.cache.k.size // self.n_blocks
                           ) * self.cache.k.dtype.itemsize
        stats = {"kv_layout": "paged", "cache_kind": self.cache_kind}
        if self.draft_cache is not None:
            dalloc = (2 * self.draft_cache.k.size
                      * self.draft_cache.k.dtype.itemsize)
            stats["draft_kv_allocated_bytes"] = dalloc
            alloc += dalloc
            block_bytes += 2 * (self.draft_cache.k.size // self.n_blocks
                                ) * self.draft_cache.k.dtype.itemsize
        a = self.manager.allocator
        stats.update({
            "kv_allocated_bytes": alloc,
            "kv_peak_resident_bytes": a.peak_in_use * block_bytes,
            "kv_block_bytes": block_bytes,
            "block_size": self.block_size, "n_blocks": self.n_blocks,
            "peak_blocks_in_use": a.peak_in_use,
            "blocks_in_use": a.n_in_use,
            "blocks_retained": len(self.manager.retained),
            "prefix_hit_tokens": self.manager.prefix_hit_tokens,
            "decode_walk_share": (self._walk_entries / self._table_entries
                                  if self._table_entries else 0.0),
            "decode_kernel": self.decode_kernel,
            "prefill_kernel": self.prefill_kernel, **shards})
        return stats

    def prefill_stats(self) -> dict:
        """Admission-path accounting: how much prompt compute actually ran
        (vs was skipped via prefix hits) and how bursty it was per step;
        where the wait for the first token went; how many steps decoded,
        and how many of those also ran a prefill chunk (``mixed_steps``);
        and how many programs the thread running ``step()`` compiled or
        loaded from the compile cache.

        Queue wait is submit to admission (a slot), prefill wait is
        admission to the first token, each over the first life of the
        requests of a bounded reservoir since :meth:`reset_stats`.  A
        request still pending, or admitted and not yet bound, enters with
        its wait so far.  Safe to call from another thread while
        ``step()`` runs: live containers are copied in one call each."""
        now = time.monotonic()
        resumed = set(self._resume_state)
        pending = self.scheduler.pending
        prefilling = tuple(self.scheduler.prefilling.values())
        queue = list(self._queue_waits) + [
            now - r.submitted_at for r in pending if r.uid not in resumed]
        prefill = list(self._prefill_waits) + [
            now - r.admitted_at for r in prefilling if r.uid not in resumed]
        q50, q95 = _p50_p95(queue)
        p50, p95 = _p50_p95(prefill)
        admitted = self._prompt_tokens_admitted
        return {
            "chunk_size": self.chunk_size,
            "buckets": list(self.buckets),
            "prefill_chunk_budget": self.prefill_chunk_budget,
            "prompt_tokens_admitted": admitted,
            "prefill_tokens_computed": self._prefill_tokens_computed,
            "prefill_tokens_padded": self._prefill_tokens_padded,
            "prefix_skipped_tokens": self._prefix_skipped_tokens,
            "prefix_hit_rate": (self._prefix_skipped_tokens / admitted
                                if admitted else 0.0),
            "prefill_chunks": self._prefill_chunks,
            "max_step_prefill_tokens": self._max_step_prefill_tokens,
            "prefill_kernel": self.prefill_kernel,
            "queue_wait_p50_s": q50,
            "queue_wait_p95_s": q95,
            "prefill_wait_p50_s": p50,
            "prefill_wait_p95_s": p95,
            "steps": self._steps,
            "decode_steps": self._decode_steps,
            "mixed_steps": self._mixed_steps,
            "programs_compiled": self._programs_compiled,
        }

    def spec_stats(self) -> dict:
        """Speculative-decoding accounting.  ``spec_acceptance_rate`` =
        accepted drafted tokens / drafted tokens; the correction token each
        round emits on top of the accepted prefix is not a draft and counts
        in neither number (so rate 1.0 means every draft verified and each
        round advanced ``spec_k`` tokens per slot)."""
        drafted = self._spec_drafted
        return {
            "spec_k": self.spec_k,
            "spec_rounds": self._spec_rounds,
            "spec_drafted_tokens": drafted,
            "spec_accepted_tokens": self._spec_accepted,
            "spec_acceptance_rate": (self._spec_accepted / drafted
                                     if drafted else 0.0),
        }

    def preempt_stats(self) -> dict:
        """Preemption accounting.  ``preempt_violations`` counts evictions
        where the victim did not outrank the preemptor's class — the
        policy guarantees 0 and the loadgen/CI gates assert it;
        ``preempted_in_flight`` is how many preempted requests currently
        await (or are mid-) resume."""
        return {
            "preemption": self.preemption,
            "preemptions": self._preemptions,
            "resumes": self._resumes,
            "preempt_violations": self._preempt_violations,
            "preempted_in_flight": len(self._resume_state),
        }

    def reset_stats(self) -> None:
        """Zero the prefill/step accounting (e.g. after a compile warmup)
        without touching the serving state.  The KV peak rebases to the
        blocks currently in use, so ``kv_peak_resident_bytes`` reflects the
        profiled traffic, not the warmup's high-water mark."""
        self.step_log = deque(maxlen=65536)
        self._preemptions = 0
        self._resumes = 0
        self._preempt_violations = 0
        self.recent_ttfts.clear()
        self._queue_waits.clear()
        self._prefill_waits.clear()
        self._steps = 0
        self._decode_steps = 0
        self._mixed_steps = 0
        self._walk_entries = 0
        self._table_entries = 0
        self._programs_compiled = 0
        self._prompt_tokens_admitted = 0
        self._prefill_tokens_computed = 0
        self._prefill_tokens_padded = 0
        self._prefix_skipped_tokens = 0
        self._prefill_chunks = 0
        self._max_step_prefill_tokens = 0
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        if self.manager is not None:
            self.manager.prefix_hit_tokens = 0
            a = self.manager.allocator
            a.peak_in_use = a.n_in_use

    # -- drivers -------------------------------------------------------------

    def run(self, max_steps: Optional[int] = None) -> list:
        """Step until every submitted request has finished."""
        out, steps = [], 0
        while not self.scheduler.idle:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return sorted(out, key=lambda c: c.uid)

    def stream(self, max_steps: Optional[int] = None,
               on_step: Optional[Callable[["ContinuousEngine"], None]] = None
               ) -> Iterator[Tuple[int, int, Optional[Completion]]]:
        """Drive the engine and yield ``(uid, token, completion)`` as
        tokens land — ``completion`` rides with a request's LAST token (and
        is ``None`` before that).  A request that finishes a step WITHOUT
        emitting a token — cancelled, or cut off by ``max_steps`` — still
        surfaces: its completion is yielded as ``(uid, None, completion)``
        after the step's token events, so no Completion is ever silently
        dropped.  Submit more requests between yields, or from ``on_step``
        (called after EVERY engine step) — a step may yield no token at
        all while prompts are mid-chunked-prefill, so a driver feeding
        timed arrivals must use the hook, not the yield points, or a long
        prefill starves the queue.  The stream drains when the scheduler
        goes idle."""
        steps = 0
        while not self.scheduler.idle:
            done = {c.uid: c for c in self.step()}
            events = self._step_events
            if on_step is not None:
                on_step(self)
            last = {uid: i for i, (uid, _) in enumerate(events)}
            for i, (uid, tok) in enumerate(events):
                comp = done.pop(uid, None) if last[uid] == i else None
                yield uid, tok, comp
            for uid, comp in done.items():  # completion-only events
                yield uid, None, comp
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break


__all__ = ["generate", "Engine", "ContinuousEngine", "Request", "Completion",
           "UnsupportedCacheError"]
