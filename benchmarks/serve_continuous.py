"""Continuous-batching serving benchmark: dense-slot vs paged KV layout,
dense-gather vs fused Pallas paged-attention decode, monolithic vs
chunked prefill, prefix-reuse compute skip.

    PYTHONPATH=src python benchmarks/serve_continuous.py            # full
    PYTHONPATH=src python benchmarks/serve_continuous.py --smoke    # CI
    PYTHONPATH=src python benchmarks/serve_continuous.py --smoke \
        --json BENCH_serve.json                                     # artifact

Four replays of one Poisson arrival trace establish the layout/kernel
matrix (all greedy tokens asserted bit-identical where applicable):

* ``dense`` — per-slot KV lanes: HBM-resident KV bytes are
  ``batch * max_len`` regardless of live tokens.
* ``paged`` — block-table layout: the KV high-water mark tracks live
  tokens (asserted >= 2x below the dense reservation).
* ``paged+pallas`` — same layout, fused paged-attention decode kernel
  (interpret mode on CPU).
* ``paged+fact@R`` — the paper's post-training use case: the model is
  SVD-factorized with ``auto_fact`` at rank ratios 0.25/0.5/0.75 and
  served through the same engine (the **rank frontier**: greedy
  agreement vs dense, tokens/s, params and per-layer reconstruction
  error per rank).  The benchmark model's singular spectra are shaped
  to a power-law decay first (``spectral_decay``, alpha=2.5): random
  init has a flat Marchenko-Pastur spectrum where truncation at any
  rank destroys the logits — the old 3% agreement number measured that
  spectrum, not a serving bug — while trained networks (the regime the
  paper compresses) decay fast.  Agreement at ratio 0.5 is asserted
  >= 0.9 and exported as ``greedy_agreement_dense_vs_fact``.
* ``paged+spec`` — speculative decoding: a rank-0.5 factorized draft
  proposes ``k`` greedy tokens per round, the dense verifier re-scores
  them in ONE multi-token decode and commits the agreeing prefix plus
  its own next token.  Greedy tokens asserted bit-identical to the
  plain paged replay; acceptance rate and draft/verify step times land
  in the summary.

Two chunked-prefill experiments then demonstrate the admission-path wins:

* **stall** — a mixed long/short trace replayed through the
  monolithic-equivalent prefill (one full-width chunk, unbounded per-step
  budget: every admission stalls decode for its whole prompt) vs the
  chunked pipeline (bounded padded tokens per step).  Asserted: identical
  greedy tokens, and the chunked path's worst per-step prefill burst —
  the deterministic stand-in for inter-decode-step stall — is both
  bounded by its budget and strictly below the monolithic burst.  Wall
  p50/p95/max per step are recorded (not asserted: CPU timing noise).
* **prefix** — a shared-system-prompt trace replayed with prefix reuse
  on vs off.  Asserted: identical greedy tokens, and prefill compute
  drops by EXACTLY the tokens served from cached prefix blocks.

A **hymba replay cell** (new-families smoke) pushes the hybrid
sliding-window + SSM family through the same continuous engine: greedy
tokens asserted identical to the one-shot ``generate`` baseline, and the
ring-KV lanes asserted resident at O(window) bytes per slot — not the
O(max_len) a dense lane would pin (the engine reports the lane length in
``kv_stats()['kv_lane_tokens']``).

An **http_serve cell** pushes the same trace through the async HTTP
front door (``repro.serve.http`` + the ``repro.launch.loadgen`` client):
closed-loop SSE completions asserted bit-identical to the offline paged
replay, then an open-loop run with Poisson arrivals and a 30% client
disconnect fraction asserted to leak zero paged blocks, with a
``/metrics`` scrape checked at the end.  Closed- and open-loop
tok/s + TTFT/latency percentiles land in ``summary["http_serve"]``.

A decode-step microbenchmark times the jitted batched decode step alone
(gather vs fused kernel) — on CPU the fused kernel runs in interpret
mode, so that timing measures overhead parity, not the TPU win.

``run()`` returns (rows, summary); ``--smoke`` uses the reduced config +
short traces (the CI gate — the long-prompt mixed trace runs there too,
so chunking is exercised in CI) and ``--json`` writes the summary for
the workflow artifact / the committed ``BENCH_serve.json``.  The summary
carries TTFT p50/p95, prefix-hit-rate, and per-step stall fields for
every variant row.

``--sharded`` runs the dp x tp sharded serving sweep instead: the same
seeded trace replayed through engines on ``{data, model}`` meshes at
every grid point of ``repro.launch.microbench.SHARDED_GRID``, tokens
asserted bit-identical to the 1x1 replay, with ``sharded_tok_s`` /
``sharded_decode_step_ms`` / ``sharded_tokens_mismatch`` cells appended
to ``--history`` for the regression gate.  On a CPU host with fewer
than 4 devices it re-execs itself under
``--xla_force_host_platform_device_count=8``; on an accelerator with
fewer than 4 devices it fails instead of falling back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import auto_fact, spectral_decay
from repro.models import build_model
from repro.serve import (ContinuousEngine, bench_trace, format_kv_stats,
                         format_prefill_stats, format_stats, generate,
                         greedy_agreement, make_trace)

try:  # repo root on sys.path (python -m benchmarks.serve_continuous)
    from benchmarks.common import speedup, timing_cell
except ImportError:  # bare script: benchmarks/ itself is sys.path[0]
    from common import speedup, timing_cell


def decode_step_ms(model, cfg, *, batch, max_len, max_prompt_len,
                   block_size, decode_kernel, iters=20, warmup=3,
                   mesh=None) -> float:
    """Mean wall time of ONE jitted batched decode step with every slot
    live — isolates the attention-gather cost from scheduler/prefill
    overhead.  Submits ``batch`` max-budget requests, admits them all,
    then drives the jitted decode directly.  With ``mesh`` the engine
    runs sharded (params/pool/state placed, activations constrained), so
    the timing includes any collective cost the partitioner inserts."""
    eng = ContinuousEngine(model, cfg, batch=batch, max_len=max_len,
                           max_prompt_len=max_prompt_len, kv_layout="paged",
                           block_size=block_size,
                           decode_kernel=decode_kernel,
                           prefill_chunk_budget=10**9, mesh=mesh)
    rng = np.random.default_rng(0)
    for _ in range(batch):
        eng.submit(rng.integers(0, cfg.vocab, max_prompt_len - 1)
                   .astype(np.int32), max_new_tokens=max_len)
    eng.step()  # admit every slot + compile the decode step
    key = eng._next_key()
    for _ in range(warmup):
        eng.cache, eng.state, nxt, _ = eng._decode(eng._models, eng.cache,
                                                   eng.state, key)
    jax.block_until_ready(nxt)
    t0 = time.perf_counter()
    for _ in range(iters):
        eng.cache, eng.state, nxt, _ = eng._decode(eng._models, eng.cache,
                                                   eng.state, key)
    jax.block_until_ready(nxt)
    return (time.perf_counter() - t0) / iters * 1e3


def spec_step_ms(model, draft, cfg, *, batch, max_prompt_len, block_size,
                 spec_k, iters=10, warmup=2) -> tuple:
    """Mean wall time of the two halves of one speculative round with
    every slot live: the k-step factorized draft scan and the single
    dense multi-token verify.  Drives the jitted pair directly (the
    engine's python bookkeeping is bypassed), so ``max_len`` is sized to
    keep every timed round's positions in range."""
    max_len = max_prompt_len + (warmup + iters + 2) * spec_k + 8
    eng = ContinuousEngine(model, cfg, batch=batch, max_len=max_len,
                           max_prompt_len=max_prompt_len, kv_layout="paged",
                           block_size=block_size,
                           prefill_chunk_budget=10**9,
                           draft_model=draft, spec_k=spec_k)
    rng = np.random.default_rng(0)
    for _ in range(batch):
        eng.submit(rng.integers(0, cfg.vocab, max_prompt_len - 1)
                   .astype(np.int32), max_new_tokens=max_len)
    eng.step()  # admit every slot + compile + run the first spec round
    draft_s = verify_s = 0.0
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        eng.draft_cache, drafts = eng._spec_draft(
            eng._models, eng.draft_cache, eng.cache.length, eng.state)
        jax.block_until_ready(drafts)
        t1 = time.perf_counter()
        out = eng._spec_verify(eng._models, eng.cache, eng.state, drafts)
        eng.cache, eng.state = out[0], out[1]
        jax.block_until_ready(out[2])
        t2 = time.perf_counter()
        if i >= warmup:
            draft_s += t1 - t0
            verify_s += t2 - t1
    return draft_s / iters * 1e3, verify_s / iters * 1e3


def http_serve_cell(model, cfg, trace, paged_done, *, dims, block_size,
                    n_open, seed) -> dict:
    """The service front door under load: the SAME trace served over HTTP
    (SSE streaming) must emit bit-identical tokens to the offline paged
    replay, and an open-loop run with client disconnects must leak zero
    paged blocks.  Returns the ``http_serve`` summary cell."""
    import asyncio

    from repro.launch.loadgen import (make_payloads, run_closed_loop,
                                      run_open_loop, summarize)
    from repro.serve.http import BackgroundServer

    def wait_drained(eng, timeout=60.0):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if eng.scheduler.idle and eng.manager.fully_free:
                return
            time.sleep(0.05)
        raise AssertionError("http engine did not drain")

    # closed loop: the trace's own requests, tokens vs the offline replay
    eng = ContinuousEngine(model, cfg, **dims, kv_layout="paged",
                           block_size=block_size)
    payloads = [{"prompt": req.prompt.tolist(),
                 "max_new_tokens": req.max_new_tokens} for _, req in trace]
    with BackgroundServer(eng, max_pending=len(payloads) + 1) as bg:
        t0 = time.perf_counter()
        closed = asyncio.run(run_closed_loop(bg.host, bg.port, payloads,
                                             concurrency=4))
        closed_wall = time.perf_counter() - t0
        for cp, r in zip(paged_done, closed):
            assert r["status"] == 200, f"http request failed: {r['error']}"
            assert r["tokens"] == cp.tokens, \
                f"http/offline divergence (prompt_len={cp.prompt_len})"
        closed_stats = summarize(closed, closed_wall)
        print(f"http closed : {closed_stats['tokens_per_s']:9.1f} tok/s   "
              f"p50 {closed_stats['latency_p50_ms']:7.1f} ms   "
              f"ttft p50 {closed_stats['ttft_p50_ms']:6.1f} ms   "
              f"({closed_stats['served']} reqs over SSE)")
        print("http serve: greedy tokens bit-identical to the offline "
              "paged replay")
        wait_drained(eng)

        # open loop with client disconnects: Poisson arrivals, a fraction
        # of clients abandon after their first token; every cancel must
        # return its blocks (pool asserted fully free afterwards)
        open_payloads = make_payloads(n_open, seed=seed + 4, min_prompt=4,
                                      max_prompt=dims["max_prompt_len"] // 2,
                                      min_new=4, max_new=8, vocab=cfg.vocab)
        t0 = time.perf_counter()
        opened = asyncio.run(run_open_loop(bg.host, bg.port, open_payloads,
                                           rate=20.0, cancel_frac=0.3,
                                           seed=seed))
        wait_drained(eng)
        open_wall = time.perf_counter() - t0
        open_stats = summarize(opened, open_wall)
        assert open_stats["errors"] == 0, f"open-loop errors: {open_stats}"
        assert eng.manager.fully_free, \
            "cancelled requests leaked paged blocks"
        n_cancel = open_stats["cancelled_by_client"]
        print(f"http open   : {open_stats['served']} served, "
              f"{n_cancel} client-cancelled, 0 leaked blocks "
              f"({open_stats['streamed_tokens']} tok streamed)")

        # the scrape endpoint works under/after load
        status, body = asyncio.run(_scrape(bg.host, bg.port))
        assert status == 200
        text = body.decode()
        for name in ("repro_serve_ttft_seconds", "repro_serve_prefix_hit_rate",
                     "repro_serve_completions_total",
                     "repro_serve_kv_blocks_in_use"):
            assert name in text, f"metric {name} missing from /metrics"
        print("http serve: /metrics scrape OK")

    return {
        "tokens_identical_to_paged_replay": True,  # asserted above
        "closed_loop": closed_stats,
        "open_loop": {**open_stats, "rate_per_s": 20.0, "cancel_frac": 0.3},
        "cancel_leaked_blocks": 0,                 # asserted fully_free
        "metrics_scrape_ok": True,                 # asserted above
    }


async def _scrape(host, port):
    from repro.launch.loadgen import fetch
    return await fetch(host, port, "/metrics")


def run(*, smoke: bool = False, fact_rank: float = 0.5, solver: str = "svd",
        seed: int = 0) -> tuple:
    cfg = get_config("paper-tiny")
    batch, max_len, max_prompt, block_size = 8, 256, 48, 16
    n_requests, load, max_new = 32, 0.5, 32
    chunk, budget = 16, 16
    long_prompt, long_frac = 48, 0.25
    step_iters = 20
    if smoke:
        cfg = cfg.reduced()
        batch, max_len, max_prompt, block_size = 4, 64, 24, 8
        n_requests, load, max_new = 8, 1.0, 6
        chunk, budget = 8, 8
        long_prompt, long_frac = 24, 0.3
        step_iters = 10

    # shape the singular spectra to the trained-network regime (random
    # init is flat Marchenko-Pastur — see the module docstring) so the
    # rank frontier below measures the paper's use case, not init noise
    model = spectral_decay(build_model(jax.random.PRNGKey(0), cfg), 2.5,
                           exclude=["embed", "lm_head"])
    trace = make_trace(n_requests, seed=seed, load=load, min_prompt=4,
                       max_prompt=max_prompt // 2, min_new=4,
                       max_new=max_new, vocab=cfg.vocab)

    rows = []
    dims = dict(batch=batch, max_len=max_len, max_prompt_len=max_prompt,
                chunk_size=chunk, prefill_chunk_budget=budget)
    dense_done, dstats = bench_trace(model, cfg, trace, **dims,
                                     kv_layout="dense")
    print(format_stats("dense-slot", dstats))
    print(format_kv_stats("dense-slot", dstats))
    rows.append({"variant": "dense-slot", **dstats})

    paged_done, pstats = bench_trace(model, cfg, trace, **dims,
                                     kv_layout="paged",
                                     block_size=block_size)
    print(format_stats("paged", pstats))
    print(format_kv_stats("paged", pstats))
    rows.append({"variant": "paged", **pstats})

    # the whole point of the layout swap: identical greedy tokens...
    for cd, cp in zip(dense_done, paged_done):
        assert cd.tokens == cp.tokens, \
            f"paged/dense divergence (prompt_len={cd.prompt_len})"
    # ...at a fraction of the resident KV footprint
    reduction = (dstats["kv_allocated_bytes"]
                 / max(pstats["kv_peak_resident_bytes"], 1))
    print(f"paged layout needs {reduction:.1f}x fewer HBM-resident KV bytes "
          f"(dense-slot reserves batch*max_len = {batch}*{max_len} lanes)")
    assert reduction >= 2.0, f"expected >= 2x KV reduction, got {reduction:.2f}x"

    # fused Pallas paged-attention decode: same trace, same greedy tokens
    fused_done, fustats = bench_trace(model, cfg, trace, **dims,
                                      kv_layout="paged",
                                      block_size=block_size,
                                      decode_kernel="pallas")
    print(format_stats("paged+pallas", fustats))
    rows.append({"variant": "paged+pallas", **fustats})
    for cp, cf in zip(paged_done, fused_done):
        assert cp.tokens == cf.tokens, \
            f"fused/gather divergence (prompt_len={cp.prompt_len})"
    print("fused pallas decode: greedy tokens bit-identical to dense gather")

    # ---- chunked-prefill win 1: bounded decode stall under long prompts ----
    mixed = make_trace(n_requests, seed=seed + 1, load=load, min_prompt=4,
                       max_prompt=max_prompt // 3, min_new=4,
                       max_new=max_new, vocab=cfg.vocab,
                       long_frac=long_frac, long_prompt=long_prompt)
    base = dict(batch=batch, max_len=max_len, max_prompt_len=max_prompt,
                kv_layout="paged", block_size=block_size)
    mono_done, mono = bench_trace(model, cfg, mixed, **base,
                                  chunk_size=max_prompt,
                                  buckets=(max_prompt,),
                                  prefill_chunk_budget=10**9)
    chunk_done, chnk = bench_trace(model, cfg, mixed, **base,
                                   chunk_size=chunk,
                                   prefill_chunk_budget=budget)
    print(format_prefill_stats("monolithic", mono))
    print(format_prefill_stats("chunked", chnk))
    rows.append({"variant": "mixed+monolithic", **mono})
    rows.append({"variant": "mixed+chunked", **chnk})
    for cm, cc in zip(mono_done, chunk_done):
        assert cm.tokens == cc.tokens, \
            f"chunked/monolithic divergence (prompt_len={cm.prompt_len})"
    stall_mono = mono["step_prefill_tokens_max"]
    stall_chnk = chnk["step_prefill_tokens_max"]
    print(f"worst per-step prefill burst: monolithic {stall_mono} tok "
          f"vs chunked {stall_chnk} tok (budget {budget})")
    assert stall_chnk <= max(budget, chunk), \
        f"chunked burst {stall_chnk} exceeds budget bound"
    assert stall_chnk < stall_mono, \
        "chunking did not reduce the per-step prefill burst"

    # ---- chunked-prefill win 2: prefix hits skip prefill compute -----------
    shared = make_trace(n_requests, seed=seed + 2, load=load, min_prompt=4,
                        max_prompt=max_prompt // 3, min_new=4,
                        max_new=max_new, vocab=cfg.vocab,
                        shared_prefix=2 * block_size)
    reuse_done, ron = bench_trace(model, cfg, shared, **base,
                                  chunk_size=chunk,
                                  prefill_chunk_budget=budget,
                                  prefix_reuse=True)
    plain_done, roff = bench_trace(model, cfg, shared, **base,
                                   chunk_size=chunk,
                                   prefill_chunk_budget=budget,
                                   prefix_reuse=False)
    print(format_prefill_stats("prefix-on", ron))
    print(format_prefill_stats("prefix-off", roff))
    rows.append({"variant": "prefix+reuse", **ron})
    rows.append({"variant": "prefix+noreuse", **roff})
    for ca, cb in zip(reuse_done, plain_done):
        assert ca.tokens == cb.tokens, \
            f"prefix-skip divergence (prompt_len={ca.prompt_len})"
    saved = (roff["prefill_tokens_computed"] - ron["prefill_tokens_computed"])
    print(f"prefix reuse skipped {ron['prefix_skipped_tokens']} prompt "
          f"tokens ({ron['prefix_hit_rate']:.0%} of admitted); prefill "
          f"compute dropped by {saved} tokens")
    assert saved == ron["prefix_skipped_tokens"] > 0, \
        "prefix-hit compute reduction must equal the skipped tokens"

    # ---- new-families smoke: hymba (ring + ssm per-slot state) -------------
    # reduced config in both modes: the cell proves the state machinery
    # (ring wraparound, ssm scan-in, slot recycling), not model-scale perf
    hy_cfg = get_config("hymba-1.5b").reduced()
    hy_model = build_model(jax.random.PRNGKey(2), hy_cfg)
    hy_max_len, hy_chunk = 64, hy_cfg.window
    hy_trace = make_trace(max(6, n_requests // 2), seed=seed + 3, load=load,
                          min_prompt=2, max_prompt=24, min_new=4,
                          max_new=max_new, vocab=hy_cfg.vocab)
    hy_done, hstats = bench_trace(hy_model, hy_cfg, hy_trace, batch=batch,
                                  max_len=hy_max_len, max_prompt_len=24,
                                  chunk_size=hy_chunk,
                                  prefill_chunk_budget=hy_chunk)
    print(format_stats("hymba-ring", hstats))
    print(format_kv_stats("hymba-ring", hstats))
    rows.append({"variant": "hymba-ring", **hstats})
    assert hstats["cache_kind"] == "hybrid"
    # ring-KV lanes are O(window) per slot, NOT O(max_len): the resident
    # ring bytes are window/max_len of what dense lanes would pin
    assert hstats["kv_lane_tokens"] == hy_cfg.window < hy_max_len
    ring_bytes = hstats["kv_ring_bytes"]
    dense_equiv = ring_bytes * hy_max_len // hy_cfg.window
    ring_reduction = dense_equiv / ring_bytes
    print(f"hymba ring KV: {ring_bytes / 1024:.1f} KiB resident "
          f"(O(window={hy_cfg.window})) vs {dense_equiv / 1024:.1f} KiB "
          f"for dense max_len={hy_max_len} lanes "
          f"({ring_reduction:.0f}x)")
    assert ring_bytes * 2 <= dense_equiv, \
        "ring lanes failed the O(window) residency bound"
    # and the tokens stay correct: every completion matches the one-shot
    # baseline (chunk == window, so boundaries land on the window edge)
    for (_, req), c in zip(hy_trace, hy_done):
        cache = hy_model.init_cache(1, hy_max_len, hy_cfg,
                                    dtype=jnp.float32)
        ref, _ = generate(hy_model, jnp.asarray(req.prompt)[None, :], cache,
                          n_steps=req.max_new_tokens)
        assert c.tokens == np.asarray(ref)[0].tolist(), \
            f"hymba replay diverged (prompt_len={req.prompt.size})"
    print("hymba ring+ssm replay: greedy tokens identical to generate")

    # decode-step microbenchmark: the gather-vs-fused number BENCH_serve
    # tracks.  Cells carry explicit provenance (compiled_backend is null
    # in interpret mode) so an interpret-mode "5x slowdown" can never
    # read as a real perf number, and the speedup below REFUSES to
    # compare across provenance mismatches.  The full sweep lives in
    # benchmarks/microbench_kernels.py -> BENCH_history.jsonl.
    step_dims = dict(batch=batch, max_len=max_len, max_prompt_len=max_prompt,
                     block_size=block_size, iters=step_iters)
    gather_cell = timing_cell(decode_step_ms(
        model, cfg, decode_kernel="reference", **step_dims))
    fused_cell = timing_cell(decode_step_ms(
        model, cfg, decode_kernel="pallas", **step_dims))
    backend = jax.default_backend()
    tag = gather_cell["compiled_backend"] or f"{backend}+interpret"
    print(f"decode step ({batch} slots, max_len {max_len}): "
          f"gather {gather_cell['ms']:.2f} ms vs fused "
          f"{fused_cell['ms']:.2f} ms "
          f"({speedup(gather_cell, fused_cell):.2f}x) [{tag}]")

    # ---- rank frontier: quality vs compression of the served model ---------
    ratios = sorted({0.25, 0.5, 0.75, fact_rank})
    frontier = []
    agree_at = {}
    for ratio in ratios:
        fact, rep = auto_fact(model, ratio, solver=solver,
                              key=jax.random.PRNGKey(1),
                              exclude=["embed", "lm_head"], gate=False,
                              return_report=True)
        fact_done, fstats = bench_trace(fact, cfg, trace, **dims,
                                        kv_layout="paged",
                                        block_size=block_size)
        assert len(fact_done) == n_requests
        agree = greedy_agreement(dense_done, fact_done)
        agree_at[ratio] = agree
        worst_err = max(e[5] for e in rep.entries)
        print(format_stats(f"fact@{ratio}", fstats))
        print(f"fact@{ratio}: agreement {agree:.1%}, "
              f"{rep.params_before:,} -> {rep.params_after:,} params "
              f"({rep.compression:.2f}x), worst layer rel_err "
              f"{worst_err:.4f}")
        rows.append({"variant": f"paged+fact@{ratio}", **fstats})
        frontier.append({
            "rank_ratio": ratio,
            "solver": solver,
            "greedy_agreement": agree,
            "tokens_per_s": fstats["tokens_per_s"],
            "params_before": rep.params_before,
            "params_after": rep.params_after,
            "compression_x": rep.compression,
            "max_layer_rel_err": worst_err,
        })
    headline = agree_at[fact_rank]
    assert headline >= 0.9, \
        f"factorized serving regressed: agreement@{fact_rank} = {headline}"

    # ---- speculative decoding: low-rank draft, dense verify ----------------
    spec_k = 4
    draft = auto_fact(model, 0.5, solver="svd",
                      exclude=["embed", "lm_head"], gate=False)
    spec_done, sstats = bench_trace(model, cfg, trace, **dims,
                                    kv_layout="paged",
                                    block_size=block_size,
                                    draft_model=draft, spec_k=spec_k)
    print(format_stats("paged+spec", sstats))
    rows.append({"variant": f"paged+spec@k{spec_k}", **sstats})
    for cp, cs in zip(paged_done, spec_done):
        assert cp.tokens == cs.tokens, \
            f"speculative divergence (prompt_len={cp.prompt_len})"
    assert sstats["spec_acceptance_rate"] > 0.0, \
        "rank-0.5 draft accepted nothing — draft path broken"
    print(f"speculative decode: k={spec_k} rounds={sstats['spec_rounds']} "
          f"accepted {sstats['spec_accepted_tokens']}"
          f"/{sstats['spec_drafted_tokens']} drafted "
          f"({sstats['spec_acceptance_rate']:.1%}); greedy tokens "
          "bit-identical to the plain paged replay")

    draft_ms, verify_ms = spec_step_ms(model, draft, cfg, batch=batch,
                                       max_prompt_len=max_prompt,
                                       block_size=block_size, spec_k=spec_k,
                                       iters=step_iters)
    print(f"spec round ({batch} slots): draft {draft_ms:.2f} ms "
          f"(k={spec_k} factorized steps) + verify {verify_ms:.2f} ms "
          f"(1 dense multi-token step)")

    # ---- HTTP front door: same trace through the async server --------------
    http_summary = http_serve_cell(model, cfg, trace, paged_done,
                                   dims=dims, block_size=block_size,
                                   n_open=max(6, n_requests // 2), seed=seed)

    # sanity: every request drained, token budgets respected
    for done in (dense_done, paged_done, fused_done, spec_done,
                 mono_done, chunk_done, reuse_done, plain_done):
        assert len(done) == n_requests
        assert all(len(c.tokens) >= 1 for c in done)
    assert len(hy_done) == len(hy_trace)

    summary = {
        "benchmark": "serve_continuous",
        "smoke": smoke,
        "backend": backend,
        "jax_version": jax.__version__,
        "config": cfg.name,
        "dims": {"batch": batch, "max_len": max_len,
                 "max_prompt_len": max_prompt, "block_size": block_size,
                 "n_requests": n_requests, "chunk_size": chunk,
                 "prefill_chunk_budget": budget,
                 "long_prompt": long_prompt, "long_frac": long_frac},
        # provenance-stamped cells, NOT bare floats: compiled_backend is
        # null when these numbers measured the Pallas interpreter
        "decode_step_ms": {"paged_gather": gather_cell,
                           "paged_pallas_fused": fused_cell},
        "kv_resident_reduction_x": reduction,
        "paged_vs_dense_tokens_identical": True,    # asserted above
        "fused_vs_gather_tokens_identical": True,   # asserted above
        "chunked_vs_monolithic_tokens_identical": True,  # asserted above
        "ttft_p50_ms": pstats["ttft_p50_ms"],
        "ttft_p95_ms": pstats["ttft_p95_ms"],
        "prefix_hit_rate": ron["prefix_hit_rate"],
        "prefix_skipped_tokens": ron["prefix_skipped_tokens"],
        "prefill_compute_saved_tokens": saved,
        "stall_step_prefill_tokens_max": {"monolithic": stall_mono,
                                          "chunked": stall_chnk},
        "stall_step_wall_p95_ms": {"monolithic": mono["step_wall_p95_ms"],
                                   "chunked": chnk["step_wall_p95_ms"]},
        "hymba_ring": {
            "cache_kind": hstats["cache_kind"],
            "window": hy_cfg.window,
            "max_len": hy_max_len,
            "kv_lane_tokens": hstats["kv_lane_tokens"],
            "ring_kv_bytes": ring_bytes,
            "dense_lane_equiv_bytes": dense_equiv,
            "ring_residency_reduction_x": ring_reduction,
            "tokens_identical_to_generate": True,  # asserted above
        },
        "greedy_agreement_dense_vs_fact": headline,
        "fact_frontier": frontier,
        "spec_decode": {
            "spec_k": spec_k,
            "draft_rank_ratio": 0.5,
            "rounds": sstats["spec_rounds"],
            "drafted_tokens": sstats["spec_drafted_tokens"],
            "accepted_tokens": sstats["spec_accepted_tokens"],
            "acceptance_rate": sstats["spec_acceptance_rate"],
            "tokens_per_s": sstats["tokens_per_s"],
            "draft_step_ms": draft_ms,
            "verify_step_ms": verify_ms,
            "tokens_identical_to_dense": True,  # asserted above
        },
        "http_serve": http_summary,
        "rows": rows,
    }
    return rows, summary


SHARDED_DIMS = dict(batch=4, max_len=48, max_prompt_len=16)


def run_sharded(*, smoke: bool = True, seed: int = 0) -> tuple:
    """The dp x tp sharded serving sweep (``--sharded``).

    Replays ONE seeded trace (chunked prefill + shared prefix) through a
    ContinuousEngine at every mesh point of
    :data:`repro.launch.microbench.SHARDED_GRID`, asserts every grid
    point's tokens bit-identical to the 1x1 replay, and times the jitted
    sharded decode step.  Returns ``(cells, summary)`` — provenance-
    stamped cells for ``BENCH_history.jsonl`` plus a JSON summary.
    """
    from repro.dist import make_serve_mesh
    from repro.launch.microbench import SHARDED_GRID, make_cell, provenance

    n_dev = len(jax.devices())
    assert n_dev >= 4, "run_sharded needs >= 4 devices (main() re-execs)"
    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    n_req = 8 if smoke else 24
    trace = make_trace(n_req, seed=seed, load=0.5, min_prompt=4,
                       max_prompt=12, min_new=2, max_new=8,
                       vocab=cfg.vocab, shared_prefix=4)
    block_size = 8
    prov = provenance()
    axes = dict(SHARDED_DIMS, block_size=block_size, requests=n_req)
    cells, grid, ref, mismatch = [], {}, None, 0
    for dp, tp in SHARDED_GRID:
        mesh = make_serve_mesh(f"{dp},{tp}")  # None at 1x1: the baseline
        variant = f"dp{dp}tp{tp}"
        rows, stats = bench_trace(model, cfg, trace, kv_layout="paged",
                                  block_size=block_size, mesh=mesh,
                                  **SHARDED_DIMS)
        toks = {r.uid: tuple(r.tokens) for r in rows}
        if ref is None:
            ref = toks
        bad = sum(1 for uid in ref if toks.get(uid) != ref[uid])
        mismatch += bad
        assert bad == 0, f"{variant}: {bad} request(s) diverged from 1x1"
        step = decode_step_ms(model, cfg, block_size=block_size,
                              decode_kernel="reference", mesh=mesh,
                              iters=(8 if smoke else 20), warmup=2,
                              **SHARDED_DIMS)
        cells.append(make_cell("sharded_tok_s", variant, axes,
                               {"value": round(stats["tokens_per_s"], 3)},
                               prov, smoke=smoke))
        cells.append(make_cell("sharded_decode_step_ms", variant, axes,
                               {"mean_ms": step}, prov, smoke=smoke))
        grid[variant] = {"devices": dp * tp,
                         "tokens_per_s": stats["tokens_per_s"],
                         "decode_step_ms": step}
    cells.append(make_cell(
        "sharded_tokens_mismatch", "total", axes,
        {"value": mismatch,
         "grid": [f"dp{d}tp{t}" for d, t in SHARDED_GRID]},
        prov, smoke=smoke))
    paths = sorted({f"{c['metric']}/{c['variant']}" for c in cells})
    cells.append(make_cell("cells_emitted", "sharded_serve", {},
                           {"value": len(cells), "paths": paths}, prov,
                           smoke=smoke))
    summary = {"suite": "sharded_serve", "smoke": smoke, "seed": seed,
               "n_devices": n_dev, "grid": grid,
               "tokens_identical_to_1x1": True,  # asserted above
               "cells": cells}
    return cells, summary


PRIORITY_DIMS = dict(batch=2, max_len=48, max_prompt_len=12)
PRIORITY_MIX = (0.25, 0.75)  # 25% class-0 urgent, 75% class-1 default


def _replay_counting_steps(model, cfg, trace, **engine_kwargs) -> tuple:
    """Replay a trace counting ENGINE STEPS, not wall time: TTFT measured
    in steps is deterministic (same seed -> same number, no CPU-timing
    flake), which is what a CI-gated scheduling comparison needs.
    Returns ``(tokens, ttft_steps)``, both keyed by trace index."""
    engine = ContinuousEngine(model, cfg, **engine_kwargs)
    pending = sorted(enumerate(trace), key=lambda p: p[1][0])
    uid_of, submit_tick, first_step = {}, {}, {}
    done, i, tick = [], 0, 0
    while i < len(pending) or not engine.scheduler.idle:
        while i < len(pending) and pending[i][1][0] <= tick:
            idx, (_, req) = pending[i]
            uid_of[idx] = engine.submit(req)
            submit_tick[idx] = tick
            i += 1
        done.extend(engine.step())
        for uid, _ in engine.step_events:
            first_step.setdefault(uid, tick)  # bind emits the first token
        tick += 1
        if tick >= 100_000:
            raise RuntimeError("priority trace did not drain")
    idx_of = {u: k for k, u in uid_of.items()}
    tokens = {idx_of[c.uid]: tuple(c.tokens) for c in done}
    ttft_steps = {idx: first_step[uid] - submit_tick[idx]
                  for idx, uid in uid_of.items() if uid in first_step}
    return tokens, ttft_steps, engine


def run_priority(*, smoke: bool = True, seed: int = 0) -> tuple:
    """Priority + preemption scheduling cells (``--priority``).

    One mixed-priority overloaded trace (25% class-0 urgent) replayed
    five ways: priority scheduling with preemption on both KV layouts,
    preemption off on both layouts, and a priority-stripped FIFO
    baseline.  Asserted/gated (all step-count based, so deterministic):

    * ``priority_ttft_regression`` — class-0 p95 TTFT (in engine steps)
      under priority scheduling minus the FIFO baseline's, clamped at 0:
      priority must beat (or tie) FIFO for the urgent class.  Hard gate 0.
    * ``resumed_tokens_mismatch`` per layout — requests whose tokens
      differ between the preemption-on and preemption-off replays; a
      resumed stream that is not bit-identical hard-fails at 0.
    * ``preempt_leaked_blocks`` / ``preempt_violations`` — 0 after drain.
    * ``preemptions`` — the scenario must actually preempt (>= 1).
    """
    from repro.launch.microbench import make_cell, provenance

    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    n_req = 12 if smoke else 24
    block_size = 4
    # load 1.0 = one expected arrival per decode step on a 2-slot batch:
    # a standing queue forms, which is the regime scheduling policy matters
    trace = make_trace(n_req, seed=seed, load=1.0, min_prompt=4,
                       max_prompt=10, min_new=4, max_new=10,
                       vocab=cfg.vocab, priority_mix=PRIORITY_MIX)
    klass = {idx: req.priority for idx, (_, req) in enumerate(trace)}
    import dataclasses as _dc
    fifo_trace = [(t, _dc.replace(r, priority=1)) for t, r in trace]

    prov = provenance()
    axes = dict(PRIORITY_DIMS, block_size=block_size, requests=n_req,
                load=1.0, priority_mix=",".join(map(str, PRIORITY_MIX)))
    paged = dict(PRIORITY_DIMS, kv_layout="paged", block_size=block_size)
    dense = dict(PRIORITY_DIMS, kv_layout="dense")

    tok_prio, ttft_prio, eng = _replay_counting_steps(
        model, cfg, trace, **paged)
    ps = eng.preempt_stats()
    leaked = eng.manager.allocator.n_in_use
    tok_off, _, _ = _replay_counting_steps(model, cfg, trace, **paged,
                                           preemption=False)
    dtok_on, _, deng = _replay_counting_steps(model, cfg, trace, **dense)
    dtok_off, _, _ = _replay_counting_steps(model, cfg, trace, **dense,
                                            preemption=False)
    _, ttft_fifo, _ = _replay_counting_steps(model, cfg, fifo_trace, **paged)

    def p95_class0(ttfts):
        vals = [s for idx, s in ttfts.items() if klass[idx] == 0]
        return float(np.percentile(np.asarray(vals), 95))

    prio_p95, fifo_p95 = p95_class0(ttft_prio), p95_class0(ttft_fifo)
    regression = max(0.0, prio_p95 - fifo_p95)
    mismatch = {
        "paged": sum(tok_prio[i] != tok_off[i] for i in tok_prio),
        "dense": sum(dtok_on[i] != dtok_off[i] for i in dtok_on),
    }
    print(f"priority    : class-0 ttft p95 {prio_p95:.0f} steps "
          f"(priority+preemption) vs {fifo_p95:.0f} steps (FIFO) "
          f"over {sum(1 for k in klass.values() if k == 0)} urgent reqs")
    print(f"preemption  : {ps['preemptions']} preempted / {ps['resumes']} "
          f"resumed, violations {ps['preempt_violations']}, "
          f"leaked blocks {leaked}, resumed-token mismatches "
          f"{mismatch['paged']} paged / {mismatch['dense']} dense")
    assert ps["preemptions"] >= 1, "overload scenario never preempted"
    assert ps["preempt_violations"] == 0
    assert leaked == 0 and deng.manager is None
    assert mismatch == {"paged": 0, "dense": 0}, mismatch
    assert prio_p95 <= fifo_p95, \
        f"priority scheduling lost to FIFO for class 0: {prio_p95} vs " \
        f"{fifo_p95} steps"

    cells = [
        make_cell("priority_ttft_regression", "class0_p95_steps", axes,
                  {"value": regression, "priority_p95_steps": prio_p95,
                   "fifo_p95_steps": fifo_p95}, prov, smoke=smoke),
        make_cell("resumed_tokens_mismatch", "paged", axes,
                  {"value": mismatch["paged"]}, prov, smoke=smoke),
        make_cell("resumed_tokens_mismatch", "dense", axes,
                  {"value": mismatch["dense"]}, prov, smoke=smoke),
        make_cell("preempt_leaked_blocks", "paged", axes,
                  {"value": leaked}, prov, smoke=smoke),
        make_cell("preempt_violations", "paged", axes,
                  {"value": ps["preempt_violations"]}, prov, smoke=smoke),
        make_cell("preemptions", "paged", axes,
                  {"value": ps["preemptions"],
                   "resumes": ps["resumes"]}, prov, smoke=smoke),
    ]
    paths = sorted({f"{c['metric']}/{c['variant']}" for c in cells})
    cells.append(make_cell("cells_emitted", "priority_serve", {},
                           {"value": len(cells), "paths": paths}, prov,
                           smoke=smoke))
    summary = {"suite": "priority_serve", "smoke": smoke, "seed": seed,
               "class0_ttft_p95_steps": {"priority": prio_p95,
                                         "fifo": fifo_p95},
               "preempt_stats": ps, "leaked_blocks": leaked,
               "resumed_tokens_mismatch": mismatch, "cells": cells}
    return cells, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + short trace (CI gate)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write the run summary as JSON (CI artifact / "
                        "BENCH_serve.json)")
    p.add_argument("--fact-rank", type=float, default=0.5)
    p.add_argument("--solver", default="svd")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sharded", action="store_true",
                   help="run the dp x tp sharded serving sweep instead of "
                        "the replay suite (on a CPU host with fewer than 4 "
                        "devices, re-execs itself under 8 forced host "
                        "devices)")
    p.add_argument("--priority", action="store_true",
                   help="run the priority + preemption scheduling cells "
                        "instead of the replay suite (step-count TTFT vs "
                        "a FIFO baseline, resumed-token bit-identity)")
    p.add_argument("--history", default="",
                   help="append the sharded/priority cells to this JSONL "
                        "perf trajectory (BENCH_history.jsonl)")
    args = p.parse_args(argv)
    if args.priority:
        cells, summary = run_priority(smoke=args.smoke, seed=args.seed)
        if args.history:
            from repro.launch.microbench import append_history
            n = append_history(args.history, cells)
            print(f"# appended {n} cells to {args.history}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2, default=float)
                f.write("\n")
            print(f"wrote summary to {args.json}")
        print("serve_continuous priority: OK")
        return 0
    if args.sharded:
        if len(jax.devices()) < 4:
            if jax.default_backend() != "cpu":
                p.error(f"--sharded needs 4 devices; {len(jax.devices())} "
                        f"{jax.default_backend()} device(s) visible")
            env = dict(os.environ)
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            env.setdefault("JAX_PLATFORMS", "cpu")
            print("# <4 devices visible; re-exec with "
                  "--xla_force_host_platform_device_count=8")
            return subprocess.run(
                [sys.executable, __file__] + list(argv or sys.argv[1:]),
                env=env).returncode
        cells, summary = run_sharded(smoke=args.smoke, seed=args.seed)
        for v, row in summary["grid"].items():
            print(f"  {v}: {row['tokens_per_s']:8.1f} tok/s   decode "
                  f"{row['decode_step_ms']:7.3f} ms/step "
                  f"({row['devices']} device(s))")
        if args.history:
            from repro.launch.microbench import append_history
            n = append_history(args.history, cells)
            print(f"# appended {n} cells to {args.history}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2, default=float)
                f.write("\n")
            print(f"wrote summary to {args.json}")
        print("serve_continuous sharded: OK")
        return 0
    _, summary = run(smoke=args.smoke, fact_rank=args.fact_rank,
                     solver=args.solver, seed=args.seed)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")
        print(f"wrote summary to {args.json}")
    print("serve_continuous: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
