"""HTTP front door + engine cancellation: differential matrix.

Two layers of coverage:

* **Engine-level cancellation** — ``ContinuousEngine.cancel`` in every
  live state (pending / mid-prefill / mid-decode), with the invariants
  the paged layout must keep: allocator refcounts return to baseline (no
  block leak), surviving requests' tokens stay bit-identical to an
  uncancelled replay, a cancelled provider's registered-but-unwritten
  prefix blocks rewind their dependents instead of deadlocking them, and
  a cancel landing on the request's final step is classified
  ``cancelled``, never ``length``.
* **HTTP-level** — the asyncio server end to end over real sockets:
  SSE tokens bit-identical to the offline baseline, 429 backpressure
  from the bounded admission queue, client-disconnect and deadline
  cancellation propagating into the engine, and the Prometheus
  ``/metrics`` + ``/healthz`` endpoints.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import BackgroundServer, ContinuousEngine, generate
from repro.launch.loadgen import (fetch, run_closed_loop, run_open_loop,
                                  sse_generate, summarize)

DIMS = dict(batch=4, max_len=64, max_prompt_len=32, block_size=8,
            chunk_size=8, prefill_chunk_budget=8)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    return model, cfg


def _engine(model, cfg, **over):
    return ContinuousEngine(model, cfg, **{**DIMS, **over})


def _baseline(model, cfg, prompt, n, max_len=64):
    cache = model.init_cache(1, max_len, cfg, dtype=jnp.float32)
    out, _ = generate(model, jnp.asarray(prompt)[None, :], cache, n_steps=n)
    return [int(t) for t in np.asarray(out)[0]]


def _prompts(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _assert_pool_clean(eng):
    """No live references, no leaked refcounts, tables all sentinel."""
    a = eng.manager.allocator
    assert eng.manager.fully_free
    assert a.n_in_use == 0
    # every refcount zero (parked LRU blocks are refcount 0 by definition)
    assert int(a.refcount.sum()) == 0
    assert (eng.manager.tables == eng.manager.sentinel).all()


# ---- engine-level cancellation matrix ---------------------------------------


def test_cancel_pending_request(setup):
    model, cfg = setup
    eng = _engine(model, cfg, batch=1)
    prompts = _prompts([6, 6, 6], cfg.vocab)
    uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()  # uid0 admitted; uid1/uid2 still pending
    assert eng.scheduler.find(uids[1])[0] == "pending"
    assert eng.cancel(uids[1])
    done = eng.run(max_steps=200)
    reasons = {c.uid: c.finish_reason for c in done}
    assert reasons[uids[1]] == "cancelled"
    assert next(c for c in done if c.uid == uids[1]).tokens == []
    # the cancelled request never occupied a slot; the others finished
    assert reasons[uids[0]] == reasons[uids[2]] == "length"
    _assert_pool_clean(eng)


def test_cancel_mid_prefill_releases_blocks(setup):
    model, cfg = setup
    eng = _engine(model, cfg)
    # 24-token prompt at chunk 8 / budget 8 => 3 steps of prefill
    prompts = _prompts([24, 8, 8], cfg.vocab, seed=1)
    uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()
    assert eng.scheduler.find(uids[0])[0] == "prefilling"
    in_use_before = eng.manager.allocator.n_in_use
    assert in_use_before > 0
    eng.cancel(uids[0])
    done = eng.run(max_steps=200)
    reasons = {c.uid: c.finish_reason for c in done}
    assert reasons[uids[0]] == "cancelled"
    assert next(c for c in done if c.uid == uids[0]).tokens == []
    # survivors bit-identical to the offline baseline
    for uid, p in zip(uids[1:], prompts[1:]):
        comp = next(c for c in done if c.uid == uid)
        assert comp.tokens == _baseline(model, cfg, p, len(comp.tokens))
    _assert_pool_clean(eng)


def test_cancel_mid_decode_survivors_bit_identical(setup):
    model, cfg = setup
    prompts = _prompts([8, 10, 6], cfg.vocab, seed=2)

    ref_eng = _engine(model, cfg)
    ref_uids = [ref_eng.submit(p, max_new_tokens=10) for p in prompts]
    ref_by_uid = {c.uid: c for c in ref_eng.run(max_steps=200)}
    ref = [ref_by_uid[u] for u in ref_uids]  # submission order

    eng = _engine(model, cfg)
    uids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(4):  # past prefill, a few decode steps in
        eng.step()
    assert eng.scheduler.find(uids[1])[0] == "running"
    eng.cancel(uids[1])
    done_by_uid = {c.uid: c for c in eng.run(max_steps=200)}
    done = [done_by_uid[u] for u in uids]

    assert done[1].finish_reason == "cancelled"
    # the cancelled request's tokens are a prefix of its uncancelled run
    n = len(done[1].tokens)
    assert 0 < n < len(ref[1].tokens)
    assert done[1].tokens == ref[1].tokens[:n]
    # survivors are untouched by the neighbour's cancellation
    for i in (0, 2):
        assert done[i].tokens == ref[i].tokens
        assert done[i].finish_reason == ref[i].finish_reason
    _assert_pool_clean(eng)


def test_cancelled_provider_rewinds_prefix_dependent(setup):
    """Cancel a prefill whose registered prefix blocks a dependent
    already hit: the dependent must rewind, recompute the orphaned span
    itself, and still produce baseline-identical tokens — not deadlock
    in blocks_ready."""
    model, cfg = setup
    # 4-token blocks/chunks: the 16-token prefix spans 4 blocks and A
    # publishes only 2 of them before the cancel lands
    eng = _engine(model, cfg, block_size=4, chunk_size=4,
                  prefill_chunk_budget=4)
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    pa = np.concatenate([prefix, rng.integers(0, cfg.vocab, 8)]).astype(
        np.int32)
    pb = np.concatenate([prefix, rng.integers(0, cfg.vocab, 4)]).astype(
        np.int32)
    ua = eng.submit(pa, max_new_tokens=4)
    eng.step()   # A admitted, first chunk in (blocks registered, pending)
    ub = eng.submit(pb, max_new_tokens=4)
    eng.step()   # B admitted: forks A's prefix blocks, waits on publish
    task_b = eng._prefills[eng.scheduler.find(ub)[1]]
    assert task_b.cached == 16 and len(task_b.hit_bids) == 4  # full chain
    assert task_b.chunks == 0  # gated by blocks_ready
    eng.cancel(ua)
    done = {c.uid: c for c in eng.run(max_steps=200)}
    assert done[ua].finish_reason == "cancelled"
    # B was rewound below its original hit boundary...
    assert task_b.cached < 16
    # ...and still completed, bit-identical to the offline baseline
    assert done[ub].finish_reason != "cancelled"
    assert done[ub].tokens == _baseline(model, cfg, pb,
                                        len(done[ub].tokens))
    _assert_pool_clean(eng)


def test_cancel_on_final_step_reports_cancelled_not_length(setup):
    model, cfg = setup
    eng = _engine(model, cfg)
    [p] = _prompts([6], cfg.vocab, seed=3)
    uid = eng.submit(p, max_new_tokens=3)
    eng.step()  # bind + first token + one decode: 2 of 3 tokens in
    assert len(eng.scheduler.slots[eng.scheduler.find(uid)[1]].tokens) == 2
    eng.cancel(uid)
    [comp] = eng.step()  # cancel drains BEFORE the would-be final decode
    assert comp.uid == uid
    assert comp.finish_reason == "cancelled"
    assert len(comp.tokens) == 2  # the final token was never produced
    _assert_pool_clean(eng)


def test_cancel_unknown_and_finished_uid_is_noop(setup):
    model, cfg = setup
    eng = _engine(model, cfg)
    [p] = _prompts([6], cfg.vocab, seed=4)
    uid = eng.submit(p, max_new_tokens=2)
    done = eng.run(max_steps=200)
    assert len(done) == 1
    assert not eng.cancel(uid)     # already finished
    assert not eng.cancel(10**9)   # never existed
    assert eng.step() == []        # draining the stale cancels is a no-op
    _assert_pool_clean(eng)


def test_stream_yields_completion_only_events(setup):
    """A cancelled request emits no token on its final step; stream()
    must still surface its Completion (as token=None) instead of
    dropping it."""
    model, cfg = setup
    eng = _engine(model, cfg)
    prompts = _prompts([6, 6], cfg.vocab, seed=5)
    ua = eng.submit(prompts[0], max_new_tokens=8)
    ub = eng.submit(prompts[1], max_new_tokens=8)
    events, cancelled_once = [], []

    def on_step(e):
        if not cancelled_once and e.scheduler.find(ub)[0] == "running":
            e.cancel(ub)
            cancelled_once.append(True)

    comps = {}
    for uid, tok, comp in eng.stream(on_step=on_step):
        events.append((uid, tok))
        if comp is not None:
            comps[uid] = (tok, comp)
    assert set(comps) == {ua, ub}
    tok_b, comp_b = comps[ub]
    assert comp_b.finish_reason == "cancelled"
    assert tok_b is None  # completion-only event: no token that step
    tok_a, comp_a = comps[ua]
    assert tok_a is not None and comp_a.finish_reason == "length"


# ---- HTTP end-to-end --------------------------------------------------------


@pytest.fixture(scope="module")
def server(setup):
    model, cfg = setup
    eng = ContinuousEngine(model, cfg, **DIMS)
    with BackgroundServer(eng, max_pending=8) as bg:
        yield bg, eng, cfg


def _wait_drained(eng, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if eng.scheduler.idle and eng.manager.fully_free:
            return
        time.sleep(0.05)
    raise AssertionError("engine did not drain")


def test_http_sse_tokens_match_offline(setup, server):
    model, cfg = setup
    bg, eng, _ = server[0], server[1], server[2]
    prompts = _prompts([8, 12, 6], cfg.vocab, seed=6)
    payloads = [{"prompt": [int(x) for x in p], "max_new_tokens": 6}
                for p in prompts]
    results = asyncio.run(run_closed_loop(bg.host, bg.port, payloads,
                                          concurrency=3))
    for p, r in zip(prompts, results):
        assert r["status"] == 200
        assert r["finish_reason"] == "length"
        assert r["tokens"] == _baseline(model, cfg, p, len(r["tokens"]))
    summary = summarize(results, 1.0)
    assert summary["served"] == 3 and summary["errors"] == 0
    _wait_drained(eng)


def test_http_healthz_and_metrics(server):
    bg, eng = server[0], server[1]

    async def drive():
        s, body = await fetch(bg.host, bg.port, "/healthz")
        assert s == 200 and b'"status": "ok"' in body
        s, body = await fetch(bg.host, bg.port, "/metrics")
        assert s == 200
        return body.decode()

    text = asyncio.run(drive())
    for name in ("repro_serve_ttft_seconds{quantile=\"0.5\"}",
                 "repro_serve_ttft_seconds{quantile=\"0.95\"}",
                 "repro_serve_latency_seconds",
                 "repro_serve_prefix_hit_rate",
                 "repro_serve_kv_blocks_in_use",
                 "repro_serve_queue_pending",
                 "repro_serve_completions_total"):
        assert name in text, f"{name} missing from /metrics"


def test_http_client_disconnect_cancels(server):
    bg, eng = server[0], server[1]

    async def drive():
        rng = np.random.default_rng(8)
        payload = {"prompt": rng.integers(0, 64, 8).tolist(),
                   "max_new_tokens": 24}
        return await sse_generate(bg.host, bg.port, payload,
                                  cancel_after_tokens=1)

    r = asyncio.run(drive())
    assert r["status"] == 200 and r["cancelled_by_client"]
    assert len(r["tokens"]) == 1
    _wait_drained(eng)  # cancel propagated: no slot, no blocks held
    assert bg.server.metrics.cancelled["disconnect"] >= 1
    assert bg.server.metrics.completions.get("cancelled", 0) >= 1


def test_http_deadline_expiry_reports_cancelled(server):
    bg, eng = server[0], server[1]

    async def drive():
        rng = np.random.default_rng(9)
        payload = {"prompt": rng.integers(0, 64, 8).tolist(),
                   "max_new_tokens": 32, "timeout_s": 0.0}
        return await sse_generate(bg.host, bg.port, payload)

    r = asyncio.run(drive())
    assert r["status"] == 200
    assert r["finish_reason"] == "cancelled"
    assert len(r["tokens"]) < 32  # the budget never ran out; the clock did
    _wait_drained(eng)
    assert bg.server.metrics.cancelled["deadline"] >= 1


def test_http_backpressure_429(setup):
    """batch=1, max_pending=1: with one request running and one queued, a
    third POST is rejected 429 before touching the engine."""
    model, cfg = setup
    eng = ContinuousEngine(model, cfg, **{**DIMS, "batch": 1})
    with BackgroundServer(eng, max_pending=1) as bg:

        async def drive():
            rng = np.random.default_rng(10)

            def payload(max_new):
                return {"prompt": rng.integers(0, cfg.vocab, 8).tolist(),
                        "max_new_tokens": max_new}

            a = asyncio.ensure_future(
                sse_generate(bg.host, bg.port, payload(48)))
            # wait until A occupies the single slot
            while eng.scheduler.n_running + eng.scheduler.n_prefilling < 1:
                await asyncio.sleep(0.01)
            b = asyncio.ensure_future(
                sse_generate(bg.host, bg.port, payload(4)))
            while eng.scheduler.n_pending < 1:  # B parked in the queue
                await asyncio.sleep(0.01)
            c = await sse_generate(bg.host, bg.port, payload(4))
            ra, rb = await a, await b
            return ra, rb, c

        ra, rb, rc = asyncio.run(drive())
        assert ra["status"] == rb["status"] == 200
        assert rc["status"] == 429
        assert "queue full" in rc["error"]
        assert bg.server.metrics.rejected_backpressure >= 1
    _wait_drained(eng)


def test_http_open_loop_with_cancels_leaks_nothing(setup):
    """The CI shape in miniature: open-loop Poisson arrivals with a
    cancel fraction; afterwards the pool is clean and the summary
    accounts for every request."""
    model, cfg = setup
    eng = ContinuousEngine(model, cfg, **DIMS)
    with BackgroundServer(eng, max_pending=16) as bg:
        rng = np.random.default_rng(11)
        payloads = [{"prompt": rng.integers(0, cfg.vocab, int(n)).tolist(),
                     "max_new_tokens": 8}
                    for n in rng.integers(4, 16, 10)]
        t0 = time.monotonic()
        results = asyncio.run(run_open_loop(bg.host, bg.port, payloads,
                                            rate=50.0, cancel_frac=0.4,
                                            seed=3))
        summary = summarize(results, time.monotonic() - t0)
        assert summary["requests"] == 10
        assert summary["errors"] == 0
        assert summary["cancelled_by_client"] >= 1
        assert summary["served"] >= 1
        _wait_drained(eng)
        assert bg.server.metrics.completions.get("cancelled", 0) >= 1
    _assert_pool_clean(eng)


# ---- metrics reservoir split + priority passthrough -------------------------


def test_metrics_split_cancelled_from_served_latency():
    """``Metrics.observe`` used to append cancelled latencies into the
    same reservoir as served ones — a storm of instant cancels dragged
    the served p50/p95 toward zero.  The reservoirs are now split."""
    from repro.serve.http import ServeMetrics
    from repro.serve.scheduler import Completion

    m = ServeMetrics()
    for i in range(4):
        m.observe(Completion(uid=i, prompt_len=4, tokens=[1] * 8,
                             finish_reason="length", priority=0,
                             submitted_at=0.0, first_token_at=1.0,
                             finished_at=10.0))
    for i in range(4, 8):  # instant cancels, never produced a token
        m.observe(Completion(uid=i, prompt_len=4, tokens=[],
                             finish_reason="cancelled",
                             submitted_at=0.0, first_token_at=0.0,
                             finished_at=0.001))
    assert list(m.latency_s) == [10.0] * 4      # served reservoir clean
    assert list(m.cancelled_latency_s) == [0.001] * 4
    assert len(m.ttft_s) == 4                   # tokenless cancels skipped
    assert set(m.ttft_by_priority) == {0}
    assert m.completions == {"length": 4, "cancelled": 4}


def test_http_metrics_expose_priority_and_preemption_series(server):
    bg, eng = server[0], server[1]

    async def drive():
        rng = np.random.default_rng(12)
        payload = {"prompt": rng.integers(0, 64, 6).tolist(),
                   "max_new_tokens": 4, "priority": 0}
        r = await sse_generate(bg.host, bg.port, payload)
        assert r["status"] == 200 and r["finish_reason"] == "length"
        _, body = await fetch(bg.host, bg.port, "/metrics")
        return body.decode()

    text = asyncio.run(drive())
    for name in ('repro_serve_cancelled_latency_seconds{quantile="0.5"}',
                 'repro_serve_ttft_seconds{quantile="0.95",priority="0"}',
                 "repro_serve_preemptions_total",
                 "repro_serve_preempt_resumes_total",
                 "repro_serve_preempt_violations_total"):
        assert name in text, f"{name} missing from /metrics"
    from repro.launch.loadgen import metric_value
    assert metric_value(text, "repro_serve_preempt_violations_total") == 0.0
    _wait_drained(eng)


def test_http_metrics_expose_wait_step_and_compile_families(server):
    """The engine's own telemetry reaches the scrape: queue and prefill
    wait quantiles, decode/mixed step counters, programs compiled."""
    bg, eng = server[0], server[1]

    async def drive():
        rng = np.random.default_rng(13)
        payload = {"prompt": rng.integers(0, 64, 20).tolist(),
                   "max_new_tokens": 4}
        r = await sse_generate(bg.host, bg.port, payload)
        assert r["status"] == 200 and r["finish_reason"] == "length"
        _, body = await fetch(bg.host, bg.port, "/metrics")
        return body.decode()

    text = asyncio.run(drive())
    values = {}
    for line in text.splitlines():
        if line.startswith("repro_serve_") and not line.startswith("#"):
            series, value = line.rsplit(" ", 1)
            values[series] = float(value)
    pf = eng.prefill_stats()
    for family in ("queue_wait", "prefill_wait"):
        p50 = values[f'repro_serve_{family}_seconds{{quantile="0.5"}}']
        p95 = values[f'repro_serve_{family}_seconds{{quantile="0.95"}}']
        assert 0 <= p50 <= p95
    assert values['repro_serve_prefill_wait_seconds{quantile="0.95"}'] > 0
    decode = values['repro_serve_steps_total{kind="decode"}']
    mixed = values['repro_serve_steps_total{kind="mixed"}']
    assert 0 <= mixed <= decode and decode >= 3
    assert decode <= pf["decode_steps"]
    assert values["repro_serve_programs_compiled_total"] >= 0
    for name in ("repro_serve_queue_wait_seconds",
                 "repro_serve_prefill_wait_seconds",
                 "repro_serve_steps_total",
                 "repro_serve_programs_compiled_total"):
        assert f"# TYPE {name} " in text, name
    _wait_drained(eng)


def test_http_priority_payload_reaches_scheduler(setup):
    """A body ``"priority"`` rides through the route into the engine: a
    class-0 POST overtakes an earlier-queued default-class request.
    (``preemption=False`` so admission order alone proves the plumbing —
    uids are issued in submission order, so the urgent request is the
    LARGEST uid yet must bind before the middle one.)"""
    model, cfg = setup
    eng = _engine(model, cfg, batch=1, preemption=False)
    with BackgroundServer(eng, max_pending=8) as bg:

        async def drive():
            rng = np.random.default_rng(13)

            def payload(prio, max_new=4):
                return {"prompt": rng.integers(0, cfg.vocab, 6).tolist(),
                        "max_new_tokens": max_new, "priority": prio}

            # a long filler holds the single slot while the queue forms
            filler = asyncio.ensure_future(
                sse_generate(bg.host, bg.port, payload(1, max_new=48)))
            while eng.scheduler.n_running + eng.scheduler.n_prefilling < 1:
                await asyncio.sleep(0.01)
            # queue: default-class first, then an urgent class-0
            low = asyncio.ensure_future(
                sse_generate(bg.host, bg.port, payload(1)))
            while eng.scheduler.n_pending < 1:
                await asyncio.sleep(0.01)
            high = asyncio.ensure_future(
                sse_generate(bg.host, bg.port, payload(0)))
            while eng.scheduler.n_pending < 2:  # both queued together
                await asyncio.sleep(0.01)
            return await asyncio.gather(filler, low, high)

        rf, rl, rh = asyncio.run(drive())
        assert all(r["status"] == 200 for r in (rf, rl, rh))
        assert all(r["finish_reason"] == "length" for r in (rf, rl, rh))
        _wait_drained(eng)
        order = list(eng.scheduler.admitted)[-3:]
        assert len(order) == 3
        assert order[1] > order[2], (
            f"urgent request did not jump the default-class queue: {order}")
    _assert_pool_clean(eng)
