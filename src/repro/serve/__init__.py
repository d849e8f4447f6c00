"""Serving subsystem: continuous batching over a paged KV cache with
chunked, prefix-aware, bucketed prefill.  (``README.md`` in this package
walks the full admission pipeline.)

Five modules:

* ``repro.serve.engine`` — device execution.  ``generate`` (one-shot
  prefill + scan decode, the equivalence baseline), ``Engine`` (lock-step
  fixed batch, kept for encdec caches and as a baseline), and
  ``ContinuousEngine``: a fixed slot batch where requests join and leave
  mid-flight.  The model's ``cache_kind(cfg)`` capability probe selects
  the per-slot state family — ``"kv"`` (paged / dense attention KV),
  ``"ring"`` (sliding-window ring lanes, O(window) per slot), ``"ssm"``
  (mamba conv/ssm recurrent state, O(1) per slot), ``"hybrid"`` (hymba:
  ring + ssm); non-KV kinds cannot be paged or prefix-cached, so those
  knobs degrade gracefully (see ``README.md`` §Cache kinds).  Prompts
  are prefilled in bucket-padded chunks (2-3 compile widths) under a
  per-step token budget, interleaved with ONE jitted batched decode
  step — a long prompt never freezes the running decode lanes.  The
  default KV layout is **paged**: all slots share a pool of
  ``block_size``-token KV blocks (``PagedKVCache.k/v: (n_layers,
  kv_heads, n_blocks, block_size, head_dim)``) and each slot maps
  logical position ``p`` to block ``table[slot, p // block_size]``, row
  ``p % block_size`` through its block-table row (``table:
  (batch, ceil(max_len / block_size))`` int32, sentinel ``n_blocks`` for
  unmapped entries); HBM spent on KV is proportional to live tokens, not
  ``batch * max_len``.  A prompt whose prefix is already resident starts
  prefilling AFTER the cached blocks (compute skipped, not just memory).
  ``kv_layout="dense"`` keeps the original per-slot lanes as the
  bit-exactness baseline, and ``decode_kernel="pallas"`` swaps the paged
  decode gather+attention for the fused
  :func:`repro.kernels.paged_attention` kernel (KV blocks stream through
  VMEM inside an online-softmax loop; greedy tokens bit-identical to the
  ``"reference"`` dense-gather path).  ``stream()`` / ``on_token`` yield
  tokens as they land.  ``draft_model``/``spec_k`` turn on greedy
  **speculative decoding**: a low-rank ``auto_fact`` draft proposes
  ``spec_k`` tokens per round and the dense model verifies them in one
  multi-token decode step — output bit-identical to plain greedy by
  construction, acceptance rate in ``spec_stats()`` (see ``README.md``
  §Factorized serving & speculative decoding).
* ``repro.serve.paging`` — host block bookkeeping.  Refcounted
  ``BlockAllocator`` over the pool, ``PrefixCache`` keyed by sha256
  hash-chains over *full* prompt blocks (``key_i = sha256(key_{i-1} ||
  block_tokens)``) so requests sharing a system prompt reuse the same
  refcounted prefill blocks (shared blocks are immutable; a request
  extends past them into freshly allocated blocks — copy-on-extend
  without the copy), and ``PagedCacheManager``, which reserves
  ``ceil(min(prompt_len + max_new, max_len) / block_size)`` blocks per
  request at admission so decode can never run out of blocks
  mid-request, reports the longest cached block-chain so prefill can
  skip it, gates same-step dependents until their provider's chunks
  publish the shared blocks, and parks freed prefix blocks on an LRU so
  hits survive idle periods.
* ``repro.serve.scheduler`` — host lifecycle.  Priority-class pending
  queues (0 = most urgent; FIFO within a class, an ``aging_every``
  starvation bound across classes), deadline-aware admission
  (``timeout_s`` drops still-queued requests at expiry), admit ->
  PREFILLING (chunks in flight) -> bind -> decode -> finish/evict,
  slot recycling.  When the block pool cannot hold the chosen head's
  reservation, admission defers (head-of-line within the class, so
  nothing starves); with ``preemption`` on, the engine instead evicts a
  strictly-lower-priority running decode and resumes it later as a
  prefix-hit re-admission (bit-identical greedy stream, merged
  Completion — see ``README.md`` §Scheduling policy).
* ``repro.serve.slo`` — ``SloBudgetAdapter``, an engine
  ``prefill_budget_hook`` that retunes ``prefill_chunk_budget`` online
  against a TTFT SLO target.
* ``repro.serve.sampling`` — the one greedy/temperature sampler every
  engine shares (Gumbel-max merge of greedy and sampled rows).
* ``repro.serve.trace`` — Poisson arrival traces (optionally with a
  shared system-prompt prefix and/or a long-prompt tail), replay,
  latency + KV-memory + admission-stall stats.
* ``repro.serve.http`` — the async HTTP front door: ``POST
  /v1/generate`` with SSE token streaming, per-request deadlines and
  client-disconnect **cancellation** (propagated into
  ``ContinuousEngine.cancel`` — slot, parked frontier, and refcounted
  paged blocks all released mid-prefill or mid-decode), a bounded
  admission queue answering 429 backpressure, and ``GET /metrics``
  Prometheus exposition of the engine stats.  ``BackgroundServer`` runs
  it on a daemon thread for synchronous callers;
  ``repro.launch.loadgen`` is the matching closed-/open-loop client.

Greedy outputs are bit-identical across ``generate``, ``Engine``, both
``ContinuousEngine`` layouts, every cache kind, and any prefill
chunking — enforced by the differential harnesses in
``tests/test_paging.py``, ``tests/test_chunked_prefill.py``, and
``tests/test_hetero_serving.py`` (hymba/mamba), with ring-buffer
invariants property-tested in ``tests/test_ring_buffer.py``.  One carve-out: capacity-factor MoE
routing is sequence-length-dependent, so MoE prompts see slightly
different expert-capacity dropping under any padding or chunking of the
prefill (this was already true of the monolithic padded prefill vs
exact-length ``generate``); the bit-identity contract covers
capacity-exact models.

Quick use::

    eng = ContinuousEngine(model, cfg, batch=8, max_len=256,
                           max_prompt_len=64, block_size=16,
                           chunk_size=32, prefill_chunk_budget=32)
    eng.submit([1, 2, 3], max_new_tokens=16)           # greedy
    eng.submit(prompt2, max_new_tokens=8, temperature=0.7, stop_ids=(0,))
    completions = eng.run()                            # drain the queue
    for uid, tok, done in eng.stream(): ...            # or stream tokens
    print(eng.kv_stats())       # resident KV bytes, prefix hits, ...
    print(eng.prefill_stats())  # chunks, computed vs skipped tokens, ...
"""

from repro.nn.attention import UnsupportedCacheError
from repro.serve.engine import ContinuousEngine, Engine, generate
from repro.serve.http import BackgroundServer, HttpServer, ServeMetrics
from repro.serve.paging import (BlockAllocator, PagedCacheManager,
                                PrefixCache, chain_keys)
from repro.serve.sampling import greedy_tokens, sample_tokens
from repro.serve.scheduler import Completion, Request, Scheduler
from repro.serve.slo import SloBudgetAdapter
from repro.serve.trace import (bench_trace, format_kv_stats,
                               format_prefill_stats, format_stats,
                               greedy_agreement, latency_stats, make_trace,
                               replay, stall_stats)

__all__ = ["Engine", "ContinuousEngine", "generate", "Request", "Completion",
           "Scheduler", "BlockAllocator", "PagedCacheManager", "PrefixCache",
           "UnsupportedCacheError", "chain_keys", "make_trace", "replay",
           "latency_stats", "stall_stats", "format_stats", "format_kv_stats",
           "format_prefill_stats", "bench_trace", "greedy_agreement",
           "greedy_tokens", "sample_tokens", "HttpServer",
           "BackgroundServer", "ServeMetrics", "SloBudgetAdapter"]
