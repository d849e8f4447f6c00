"""Serving driver: continuous batching with a (optionally factorized) model.

    PYTHONPATH=src python -m repro.launch.serve --arch paper-tiny \
        --batch 8 --max-len 256 --n-requests 32 \
        [--kv-layout paged --block-size 16 --decode-kernel pallas] \
        [--chunk-size 32 --buckets 8,16,32 --prefill-budget 32] \
        [--no-prefix-reuse --prefix-retain 64] [--stream] \
        [--factorize --rank 0.5 --solver svd] [--spec-k 4]

Replays a Poisson arrival trace of variable-length prompts through the
continuous-batching engine (``repro.serve.ContinuousEngine``): requests are
admitted into recyclable slots mid-flight under one jitted decode step and
a **chunked, bucketed prefill** — prompts are consumed ``--chunk-size``
tokens at a time (each span right-padded to a width from ``--buckets``, so
the chunk jit compiles at 2-3 widths), spending at most
``--prefill-budget`` padded tokens per engine step so a long prompt's
prefill interleaves with decode instead of stalling it.

The default KV layout is **paged** — slots share a pool of
``--block-size``-token KV blocks through per-slot block tables, with
refcounted prefix caching for shared prompt prefixes — so HBM-resident KV
bytes track live tokens instead of ``batch * max_len``.  Prefix hits skip
the *compute* too: prefill starts after the longest cached block-chain
(``--no-prefix-reuse`` disables), and freed prefix blocks stay parked on
an LRU (``--prefix-retain`` blocks; default the whole pool) so hits
survive idle periods.  ``--kv-layout dense`` restores the per-slot lanes
for comparison; both layouts produce bit-identical greedy tokens.
``--decode-kernel pallas`` swaps the paged decode attention from the
dense-gather reference to the fused Pallas kernel
(``repro.kernels.paged_attention`` — interpret mode off-TPU; greedy
tokens stay bit-identical).  ``--prefill-kernel pallas`` does the same
for the chunked-prefill attention on EITHER KV layout
(``repro.kernels.chunk_attention``, flash-style online softmax over the
resident prefix + the chunk's fresh K/V — greedy tokens stay
bit-identical).  ``--shared-prefix N`` gives every prompt one
common N-token system prefix to exercise the prefix cache;
``--long-frac/--long-prompt`` mix in a heavy prompt tail to exercise
chunking.

**Heterogeneous families.**  ``--arch hymba-1.5b`` (hybrid sliding-window
attention + SSM) and ``--arch mamba2-2.7b`` (pure SSM) serve through the
same engine via per-slot state — ring-buffer KV lanes (O(window) per
slot) and/or conv/ssm recurrent state (O(1) per slot).  These state
kinds cannot be paged or prefix-cached, so the engine degrades the paged
knobs gracefully (prefix reuse auto-off, block reservation skipped) and
reports the effective ``cache_kind`` in its stats; ``--decode-kernel
pallas`` is attention-paged-only and ``--prefill-kernel pallas`` needs
position-addressable KV lanes — both error for these families.

**Scheduling policy.**  ``--priority-mix 0.2,0.8`` samples per-request
priority classes into the trace (class 0 = most urgent; FIFO within a
class, ``--aging-every`` bounds cross-class starvation), and the engine
preempts running decodes of a strictly lower class when a higher-class
head is blocked — the victim's committed blocks park on the prefix-cache
LRU and it resumes later as a prefix-hit re-admission with a
bit-identical greedy stream (``--no-preemption`` disables).
``--slo-ttft S`` plugs in the SLO adapter that retunes
``--prefill-budget`` online against an observed-TTFT p95 target.  See
``src/repro/serve/README.md`` §Scheduling policy.

``--stream`` switches from batch replay to the streaming API: tokens are
printed as SSE-style ``data:`` lines the moment they land
(``ContinuousEngine.stream()`` / ``on_token``).

``--http`` boots the real network front door instead of a local replay:
an asyncio HTTP server (``repro.serve.http``) on ``--host``/``--port``
serving ``POST /v1/generate`` (SSE token streaming, per-request
deadlines, client-disconnect cancellation), ``GET /metrics`` (Prometheus
text: TTFT/latency quantiles, prefix-hit rate, KV blocks in use), and
``GET /healthz``.  ``--max-pending`` bounds the admission queue (a full
queue answers 429 with ``Retry-After`` — backpressure instead of
unbounded buffering) and ``--request-timeout`` sets the default
per-request deadline in seconds (0 = none; an expired request is
cancelled and reported ``finish_reason="cancelled"``).  Drive it with
``python -m repro.launch.loadgen`` (closed- and open-loop client).
``--http`` serves the model the other flags select — including
``--factorize`` and ``--spec-k`` variants — and ignores the trace knobs
(clients bring the traffic).

Demonstrates the paper's post-training-factorization use case end-to-end —
``--factorize`` SVD-factorizes the dense model *after* "training" (here:
at init; rank ``--rank`` as a ratio of min(m, n), embed/lm_head kept
dense, r_max gate off so ``--rank 1.0`` reconstructs exactly) and serves
it through the same engine, reporting dense-vs-factorized greedy
agreement alongside tokens/s, p50/p95 latency, TTFT, HBM-resident KV
bytes, and the admission-path profile.  ``--fact-rank R`` is the
deprecated spelling of ``--factorize --rank R``.

``--spec-k K`` turns on **speculative decoding**: a ``--rank``-ratio
factorized draft of the model proposes K greedy tokens per round and the
dense model verifies them in ONE batched multi-token decode step,
committing the agreeing prefix plus its own next token — the greedy
output is bit-identical to plain dense decoding by construction (the
driver asserts it), and the acceptance rate printed per run is the
fraction of drafted tokens the verifier kept.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.core import auto_fact
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serve import (ContinuousEngine, bench_trace, format_kv_stats,
                         format_prefill_stats, format_stats,
                         greedy_agreement, make_trace)


def stream_trace(model, cfg, trace, *, out=sys.stdout, **dims) -> int:
    """SSE-style streaming driver: replay ``trace`` through
    ``ContinuousEngine.stream()``, printing one ``data:`` line per landed
    token and an ``event: done`` line per completion.  Returns the number
    of streamed tokens."""
    engine = ContinuousEngine(model, cfg, **dims)
    pending = sorted(trace, key=lambda p: p[0])
    i, n_tok, ticks = 0, 0, 0

    def feed(_eng=None) -> None:
        """Submit every arrival due by the step clock (ticks once per
        engine step via the on_step hook — step_log itself is a bounded
        deque, so its length cannot serve as a clock)."""
        nonlocal i, ticks
        if _eng is not None:
            ticks += 1
        while i < len(pending) and pending[i][0] <= ticks:
            engine.submit(pending[i][1])
            i += 1

    feed()
    while i < len(pending) or not engine.scheduler.idle:
        # feed through the on_step hook, not the yield points: a step can
        # produce no token while prompts are mid-chunked-prefill, and timed
        # arrivals must keep flowing into the free slots regardless
        for uid, tok, comp in engine.stream(on_step=feed):
            if tok is not None:  # None = completion-only event (cancelled)
                n_tok += 1
                print(f"data: {json.dumps({'id': uid, 'token': tok})}",
                      file=out)
            if comp is not None:
                done = {"id": uid, "reason": comp.finish_reason,
                        "n_tokens": len(comp.tokens)}
                print(f"event: done\ndata: {json.dumps(done)}", file=out)
        if i < len(pending) and engine.scheduler.idle:
            # idle gap: jump the clock to the next arrival, so the burst
            # due around it still batches instead of trickling in late
            ticks = max(ticks, int(np.ceil(pending[i][0])))
            feed()
    return n_tok


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-tiny")
    p.add_argument("--batch", type=int, default=8,
                   help="decode slots (requests in flight)")
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--max-prompt-len", type=int, default=64)
    p.add_argument("--n-requests", type=int, default=32)
    p.add_argument("--load", type=float, default=0.5,
                   help="expected request arrivals per decode step")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--kv-layout", choices=("paged", "dense"),
                   default="paged")
    p.add_argument("--block-size", type=int, default=16,
                   help="tokens per KV block (paged layout)")
    p.add_argument("--n-blocks", type=int, default=0,
                   help="KV pool size; 0 = batch * ceil(max_len/block_size)")
    p.add_argument("--decode-kernel", choices=("reference", "pallas"),
                   default="reference",
                   help="paged decode attention: dense-gather reference or "
                        "the fused Pallas paged-attention kernel")
    p.add_argument("--prefill-kernel", choices=("reference", "pallas"),
                   default="reference",
                   help="chunked-prefill attention (paged or dense KV): "
                        "dense-gather reference or the flash Pallas "
                        "prefill-chunk kernel")
    p.add_argument("--chunk-size", type=int, default=32,
                   help="max prompt tokens consumed per prefill chunk")
    p.add_argument("--buckets", default="",
                   help="comma-separated chunk compile widths "
                        "(default: chunk_size and its halvings)")
    p.add_argument("--prefill-budget", type=int, default=0,
                   help="max padded prefill tokens per engine step "
                        "(0 = chunk_size); decode advances in between")
    p.add_argument("--no-prefix-reuse", action="store_true",
                   help="disable prefix-cache compute skip AND retention")
    p.add_argument("--prefix-retain", type=int, default=-1,
                   help="freed prefix blocks kept warm on the LRU "
                        "(-1 = whole pool, 0 = recycle immediately)")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="common system-prompt tokens prepended to every "
                        "request (prefix-cache workload)")
    p.add_argument("--long-frac", type=float, default=0.0,
                   help="fraction of requests drawn as long prompts")
    p.add_argument("--long-prompt", type=int, default=0,
                   help="prompt length of the long fraction "
                        "(default: max_prompt_len minus the shared prefix)")
    p.add_argument("--priority-mix", default="",
                   help="comma-separated weights over priority classes "
                        "0..k-1 (0 = most urgent), sampled per trace "
                        "request — e.g. '0.2,0.8' = 20%% urgent traffic "
                        "(empty = everything class 1)")
    p.add_argument("--no-preemption", action="store_true",
                   help="disable decode preemption: a blocked higher-"
                        "priority head waits instead of evicting a "
                        "lower-priority running decode")
    p.add_argument("--aging-every", type=int, default=16,
                   help="starvation bound: the oldest pending class head "
                        "is bypassed by at most this many consecutive "
                        "admissions before being forced to run")
    p.add_argument("--slo-ttft", type=float, default=0.0,
                   help="TTFT SLO target in seconds: adapts the prefill "
                        "chunk budget online against the observed p95 "
                        "(repro.serve.slo.SloBudgetAdapter; 0 = off)")
    p.add_argument("--stream", action="store_true",
                   help="print tokens as SSE-style data: lines as they "
                        "land instead of batch stats")
    p.add_argument("--http", action="store_true",
                   help="serve over HTTP instead of replaying a trace: "
                        "POST /v1/generate (SSE streaming, deadlines, "
                        "disconnect cancellation), GET /metrics "
                        "(Prometheus), GET /healthz; drive with "
                        "repro.launch.loadgen")
    p.add_argument("--host", default="127.0.0.1",
                   help="HTTP bind address (--http)")
    p.add_argument("--port", type=int, default=8000,
                   help="HTTP port; 0 picks an ephemeral one (--http)")
    p.add_argument("--max-pending", type=int, default=64,
                   help="admission queue bound; a full queue answers "
                        "429 backpressure (--http)")
    p.add_argument("--request-timeout", type=float, default=0.0,
                   help="default per-request deadline in seconds; an "
                        "expired request is cancelled (0 = none, --http)")
    p.add_argument("--factorize", action="store_true",
                   help="serve the auto_fact-factorized model (rank from "
                        "--rank, embed/lm_head excluded, r_max gate off so "
                        "--rank 1.0 is an exact full-rank factorization) "
                        "and report dense-vs-factorized greedy agreement")
    p.add_argument("--rank", type=float, default=0.5,
                   help="factorization rank as a ratio of min(m, n) per "
                        "layer (1.0 = exact full rank)")
    p.add_argument("--fact-rank", type=float, default=0.0,
                   help="deprecated alias for --factorize --rank R")
    p.add_argument("--solver", default="svd",
                   choices=("svd", "snmf", "random"))
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding draft depth: a rank---rank "
                        "factorized draft proposes k tokens per round, the "
                        "dense model verifies them in one multi-token step "
                        "(greedy output stays bit-identical; 0 = off)")
    p.add_argument("--mesh", default="",
                   help="serving device mesh 'dp,tp' ({data, model} axes; "
                        "e.g. '2,2' = 2-way data x 2-way tensor "
                        "parallelism over the first 4 devices).  Empty = "
                        "single-device.  Defaults to $REPRO_MESH.  "
                        "CPU-testable: export XLA_FLAGS="
                        "--xla_force_host_platform_device_count=8")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--reduced", action="store_true")
    args = p.parse_args(argv)
    if args.fact_rank:  # pre-PR6 spelling
        args.factorize, args.rank = True, args.fact_rank
    use_compile_cache()

    min_prompt = 4
    if not 0 <= args.shared_prefix <= args.max_prompt_len - min_prompt:
        p.error(f"--shared-prefix must be in [0, {args.max_prompt_len} - "
                f"{min_prompt}] so prompts still fit --max-prompt-len")
    if args.kv_layout != "paged" and args.decode_kernel != "reference":
        p.error("--decode-kernel pallas requires --kv-layout paged")
    long_prompt = args.long_prompt or args.max_prompt_len - args.shared_prefix
    if not 0 < long_prompt <= args.max_prompt_len - args.shared_prefix:
        p.error("--long-prompt must fit --max-prompt-len with the prefix")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    kind = getattr(model, "cache_kind", lambda c: None)(cfg)
    if kind not in (None, "kv"):
        if args.decode_kernel == "pallas":
            p.error(f"--decode-kernel pallas needs paged attention KV; "
                    f"{args.arch} serves via per-slot {kind!r} state")
        if args.prefill_kernel == "pallas":
            p.error(f"--prefill-kernel pallas needs position-addressable "
                    f"attention KV; {args.arch} serves via per-slot "
                    f"{kind!r} state")
        if args.spec_k:
            p.error(f"--spec-k needs a multi-token-capable KV cache; "
                    f"{args.arch} serves via per-slot {kind!r} state")
        print(f"# {args.arch}: per-slot {kind!r} state — paged layout / "
              "prefix cache knobs inactive")
    priority_mix = (tuple(float(w) for w in args.priority_mix.split(","))
                    if args.priority_mix else None)
    trace = make_trace(args.n_requests, seed=args.seed, load=args.load,
                       min_prompt=min_prompt,
                       max_prompt=args.max_prompt_len - args.shared_prefix,
                       min_new=4, max_new=args.max_new, vocab=cfg.vocab,
                       shared_prefix=args.shared_prefix,
                       long_frac=args.long_frac, long_prompt=long_prompt,
                       priority_mix=priority_mix)

    dims = dict(batch=args.batch, max_len=args.max_len,
                max_prompt_len=args.max_prompt_len,
                kv_layout=args.kv_layout, chunk_size=args.chunk_size,
                preemption=not args.no_preemption,
                aging_every=args.aging_every)
    if args.slo_ttft:
        from repro.serve import SloBudgetAdapter
        dims["prefill_budget_hook"] = SloBudgetAdapter(args.slo_ttft)
    if args.prefill_kernel != "reference":
        # both KV layouts take the flash prefill-chunk kernel; per-slot
        # ring/ssm families were rejected above
        dims["prefill_kernel"] = args.prefill_kernel
    if args.buckets:
        dims["buckets"] = tuple(int(b) for b in args.buckets.split(","))
    if args.prefill_budget:
        dims["prefill_chunk_budget"] = args.prefill_budget
    if args.kv_layout == "paged":
        dims["block_size"] = args.block_size
        dims["decode_kernel"] = args.decode_kernel
        dims["prefix_reuse"] = not args.no_prefix_reuse
        if args.n_blocks:
            dims["n_blocks"] = args.n_blocks
        if args.prefix_retain >= 0:
            dims["prefix_retain_blocks"] = args.prefix_retain

    from repro.dist.runtime import global_config, make_serve_mesh
    if args.mesh:
        global_config.mesh_spec = args.mesh
    try:
        mesh = make_serve_mesh()
    except ValueError as e:
        p.error(str(e))
    if mesh is not None:
        if (mesh.shape["model"] > 1
                and "pallas" in (args.decode_kernel, args.prefill_kernel)):
            p.error("pallas kernels are single-shard; use the reference "
                    "kernels with a model (tp) axis > 1")
        dims["mesh"] = mesh
        print(f"# mesh: data={mesh.shape['data']} x "
              f"model={mesh.shape['model']} on "
              f"{mesh.devices.size} {mesh.devices.flat[0].platform} devices")

    if args.http:
        if args.stream:
            p.error("--http and --stream are mutually exclusive")
        from repro.serve.http import serve as http_serve
        serve_model = model
        if args.factorize:
            serve_model = auto_fact(model, args.rank, solver=args.solver,
                                    key=jax.random.PRNGKey(1),
                                    exclude=["embed", "lm_head"], gate=False)
        if args.spec_k:
            dims["draft_model"] = auto_fact(
                serve_model, args.rank, solver=args.solver,
                key=jax.random.PRNGKey(1),
                exclude=["embed", "lm_head"], gate=False)
            dims["spec_k"] = args.spec_k
        engine = ContinuousEngine(serve_model, cfg, **dims)
        # compile warmup, one prompt per reachable bucket width (mirrors
        # bench_trace): the first live request must not pay the jit
        for plen in sorted({min(w, args.max_prompt_len)
                            for w in engine.buckets}):
            engine.submit(np.zeros(plen, np.int32), max_new_tokens=2)
        engine.run()
        engine.reset_stats()
        http_serve(engine, host=args.host, port=args.port,
                   max_pending=args.max_pending,
                   default_timeout_s=args.request_timeout or None)
        return 0

    if args.stream:
        if args.spec_k:
            p.error("--stream and --spec-k are mutually exclusive (the "
                    "streaming driver replays the plain decode path)")
        n_tok = stream_trace(model, cfg, trace, **dims)
        print(f": streamed {n_tok} tokens from {args.n_requests} requests")
        return 0

    dense_done, stats = bench_trace(model, cfg, trace, **dims)
    print(format_stats("dense", stats))
    print(format_kv_stats("dense", stats))
    print(format_prefill_stats("dense", stats))
    if priority_mix or stats.get("preemptions"):
        print(f"{'scheduling':11s}: {stats['preemptions']} preempted / "
              f"{stats['resumes']} resumed, "
              f"violations {stats['preempt_violations']} (must be 0)")

    if args.factorize:
        fact, report = auto_fact(model, args.rank, solver=args.solver,
                                 key=jax.random.PRNGKey(1),
                                 exclude=["embed", "lm_head"], gate=False,
                                 return_report=True)
        print(report.summary())
        fact_done, fstats = bench_trace(fact, cfg, trace, **dims)
        print(format_stats("factorized", fstats))
        print(format_kv_stats("factorized", fstats))
        print(format_prefill_stats("factorized", fstats))
        agree = greedy_agreement(dense_done, fact_done)
        print(f"greedy token agreement dense vs factorized: {agree:.1%}")

    if args.spec_k:
        # low-rank draft + dense verify: same greedy tokens, fewer rounds
        draft = auto_fact(model, args.rank, solver=args.solver,
                          key=jax.random.PRNGKey(1),
                          exclude=["embed", "lm_head"], gate=False)
        spec_done, sstats = bench_trace(model, cfg, trace, **dims,
                                        draft_model=draft,
                                        spec_k=args.spec_k)
        print(format_stats("speculative", sstats))
        print(f"speculative decode: k={sstats['spec_k']} "
              f"rounds={sstats['spec_rounds']} "
              f"accepted {sstats['spec_accepted_tokens']}"
              f"/{sstats['spec_drafted_tokens']} drafted "
              f"({sstats['spec_acceptance_rate']:.1%})")
        agree = greedy_agreement(dense_done, spec_done)
        print(f"greedy token agreement dense vs speculative: {agree:.1%}")
        assert agree == 1.0, "speculative decoding must be bit-exact"
    return 0


if __name__ == "__main__":
    sys.exit(main())
