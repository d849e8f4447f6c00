"""The serving layer's own telemetry: request lifecycle stamps, the queue
and prefill wait reservoirs of ``prefill_stats()``, the step counters and
``step_log`` fields, compile accounting, the ``serve.*`` host spans and
the ``serve.*`` named scopes of the jitted roles."""

import asyncio
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.serve import BackgroundServer, ContinuousEngine
from repro.launch.loadgen import sse_generate

DIMS = dict(batch=2, max_len=48, max_prompt_len=32, block_size=8,
            chunk_size=8)
PHASES = {"serve.admit", "serve.table_flush", "serve.prefill_chunk",
          "serve.bind", "serve.decode", "serve.host_sync", "serve.emit"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    return model, cfg


def _engine(setup, **over):
    model, cfg = setup
    return ContinuousEngine(model, cfg, **{**DIMS, **over})


def _prompts(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _submit(eng, lengths, vocab, max_new=4, seed=0, **kw):
    return [eng.submit(p, max_new_tokens=max_new, **kw)
            for p in _prompts(lengths, vocab, seed)]


def test_lifecycle_stamps_are_ordered(setup):
    eng = _engine(setup)
    _submit(eng, [20, 5, 12, 3], setup[1].vocab)
    out = eng.run()
    assert len(out) == 4
    for c in out:
        assert 0 < c.submitted_at <= c.admitted_at <= c.first_token_at \
            <= c.finished_at, c


def test_pending_request_enters_queue_wait_with_its_wait_so_far(setup):
    eng = _engine(setup)
    _submit(eng, [20], setup[1].vocab)
    time.sleep(0.05)
    st = eng.prefill_stats()
    assert st["queue_wait_p50_s"] >= 0.05 and st["steps"] == 0
    assert st["prefill_wait_p95_s"] == 0.0  # nothing admitted yet
    eng.step()  # admitted, 1 of 3 chunks: not bound
    assert eng.scheduler.n_prefilling == 1
    time.sleep(0.05)
    st = eng.prefill_stats()
    assert st["prefill_wait_p50_s"] >= 0.05
    # the admitted request's wait is now a sample, no longer live
    assert st["queue_wait_p50_s"] == pytest.approx(
        eng.scheduler.prefilling[0].admitted_at
        - eng.scheduler.prefilling[0].submitted_at)


def test_preempted_resume_adds_no_wait_sample(setup):
    model, cfg = setup
    eng = ContinuousEngine(model, cfg, batch=2, max_len=32,
                           max_prompt_len=12, block_size=4)
    low = _submit(eng, [8, 8], cfg.vocab, max_new=12, priority=2)
    for _ in range(8):
        eng.step()
    assert eng.scheduler.n_running == 2
    first_admit = {r.uid: r.admitted_at for r in
                   (s.request for s in eng.scheduler.slots)}
    high = _submit(eng, [6], cfg.vocab, max_new=6, seed=1, priority=0)
    eng.step()
    assert eng.preempt_stats()["preemptions"] == 1
    (resumed,) = (r.uid for r in eng.scheduler.pending)
    assert resumed in low and eng.scheduler.find(high[0])[0] != "pending"
    time.sleep(0.2)
    # the resumes wait in the queue again, but their first life's wait
    # was sampled at their first admission: they do not enter live
    assert eng.prefill_stats()["queue_wait_p95_s"] < 0.2
    out = {c.uid: c for c in eng.run()}
    assert len(eng._queue_waits) == 3 and len(eng._prefill_waits) == 3
    for uid in low:
        assert out[uid].preemptions == (uid == resumed)
        assert out[uid].admitted_at == first_admit[uid]
        assert out[uid].admitted_at <= out[uid].first_token_at


def test_decode_walk_share_counts_only_the_walked_table(setup):
    """One request on three slots: the two idle slots walk no table entry,
    and the running slot walks up to its position's block, so the share
    is exactly that sum over ``batch x max_table`` a decode step."""
    plen, max_new, bs = 20, 6, DIMS["block_size"]
    eng = _engine(setup, batch=3)
    assert eng.kv_stats()["decode_walk_share"] == 0.0  # no decode step yet
    _submit(eng, [plen], setup[1].vocab, max_new=max_new)
    eng.run()
    steps = eng.prefill_stats()["decode_steps"]
    assert steps == max_new - 1  # the first token comes from the prefill
    reserved = -(-(plen + max_new) // bs)
    walked = sum(min((plen + t) // bs + 1, reserved) for t in range(steps))
    share = eng.kv_stats()["decode_walk_share"]
    assert 0 < share <= 1
    assert share == pytest.approx(
        walked / (steps * 3 * eng.manager.max_table))
    eng.reset_stats()
    assert eng.kv_stats()["decode_walk_share"] == 0.0


def test_mixed_steps_count_decode_steps_that_ran_a_chunk(setup):
    eng = _engine(setup)
    _submit(eng, [20, 5, 30, 3], setup[1].vocab, max_new=6)
    eng.run()
    log = list(eng.step_log)
    st = eng.prefill_stats()
    assert st["steps"] == len(log)
    assert st["decode_steps"] == sum(e["decoded_slots"] > 0 for e in log)
    assert st["mixed_steps"] == sum(e["decoded_slots"] > 0 and e["chunks"] > 0
                                    for e in log)
    assert sum(e["chunks"] for e in log) == st["prefill_chunks"]
    # the first step only chunks; later ones decode with and without one
    assert log[0]["chunks"] and not log[0]["decoded_slots"]
    assert 0 < st["mixed_steps"] < st["decode_steps"]
    assert [e["t0"] for e in log] == sorted(e["t0"] for e in log)
    assert all(b["t0"] >= a["t0"] + a["wall_s"] for a, b in zip(log, log[1:]))


def test_unwarmed_bucket_compiles_once_in_its_phase(setup):
    vocab = setup[1].vocab
    eng = _engine(setup, batch=1)
    _submit(eng, [16], vocab)      # chunks of width 8 only
    eng.run()
    eng.reset_stats()
    _submit(eng, [16], vocab, seed=1)
    eng.run()
    assert eng.prefill_stats()["programs_compiled"] == 0
    assert all(e["compiles"] == [] for e in eng.step_log)
    _submit(eng, [3], vocab, seed=2)  # a final chunk of width 4
    eng.run()
    assert eng.prefill_stats()["programs_compiled"] == 1
    assert [e["compiles"] for e in eng.step_log if e["compiles"]] == [
        ["serve.prefill_chunk"]]


def test_reset_stats_clears_the_telemetry(setup):
    eng = _engine(setup)
    _submit(eng, [20, 5, 12], setup[1].vocab)
    eng.run()
    st = eng.prefill_stats()
    assert st["steps"] and st["programs_compiled"] and st["mixed_steps"]
    assert st["queue_wait_p95_s"] > 0 and st["prefill_wait_p95_s"] > 0
    eng.reset_stats()
    st = eng.prefill_stats()
    for key in ("steps", "decode_steps", "mixed_steps", "programs_compiled",
                "queue_wait_p50_s", "queue_wait_p95_s",
                "prefill_wait_p50_s", "prefill_wait_p95_s"):
        assert st[key] == 0, key
    assert len(eng.step_log) == 0 and len(eng.recent_ttfts) == 0


def test_prefill_stats_from_another_thread_while_stepping(setup):
    eng = _engine(setup, batch=4)
    _submit(eng, [20, 5, 12, 3, 30, 9, 17, 2] * 2, setup[1].vocab,
            max_new=8)
    errors, calls, stop = [], [0], threading.Event()

    def poll():
        while not stop.is_set():
            try:
                eng.prefill_stats()
                calls[0] += 1
            except Exception as exc:  # the assertion below reports it
                errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    poller = threading.Thread(target=poll)
    poller.start()
    try:
        out = eng.run()
    finally:
        stop.set()
        poller.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not poller.is_alive()
    assert errors == [] and calls[0] > 0
    assert len(out) == 16


def _serve_spans(trace_dir):
    """``(name, start, end, thread line)`` of every ``serve.*`` host span
    of the newest trace under ``trace_dir``."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, line.name))
    return out


def test_phase_spans_nest_inside_the_step_span(setup, tmp_path):
    eng = _engine(setup)
    _submit(eng, [20, 5, 12, 3], setup[1].vocab)
    eng.run()  # compile outside the trace
    eng.reset_stats()
    _submit(eng, [20, 5, 12, 3], setup[1].vocab, seed=3)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = _serve_spans(tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(eng.step_log)
    phases = [s for s in spans if s[0] != "serve.step"]
    assert {s[0] for s in phases} == PHASES
    for name, s, e, line in phases:
        assert any(s0 <= s and e <= e0 and line == l0
                   for _, s0, e0, l0 in steps), name
    chunk = [s for s in phases if s[0] == "serve.prefill_chunk"]
    assert len(chunk) == eng.prefill_stats()["prefill_chunks"]


def test_http_pump_opens_its_fanout_span(setup, tmp_path):
    eng = _engine(setup)
    prompt = _prompts([6], setup[1].vocab)[0].tolist()
    with BackgroundServer(eng) as bg:
        with jax.profiler.trace(str(tmp_path)):
            r = asyncio.run(sse_generate(
                bg.host, bg.port, {"prompt": prompt, "max_new_tokens": 4}))
    assert r["status"] == 200 and len(r["tokens"]) == 4
    names = [s[0] for s in _serve_spans(tmp_path)]
    assert "serve.fanout" in names and "serve.step" in names


def test_roles_keep_their_module_names_and_carry_their_scope(setup):
    """``bench/work.py`` finds the step programs by module name
    (``decode_fn``, ``lambda``); the scopes ride in the op paths."""
    eng = _engine(setup, kv_layout="dense")
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    toks = jnp.zeros((1, 8), jnp.int32)
    lowered = {
        "serve.decode": eng._decode.lower(eng._models, eng.cache, eng.state,
                                          eng._next_key()),
        "serve.chunk_mid": eng._chunk_mid.lower(
            eng._models, toks, eng.cache, i32(0), i32(0), i32(8)),
        "serve.chunk_last": eng._chunk_last.lower(
            eng._models, toks, eng.cache, i32(0), i32(0), i32(8)),
        "serve.bind": eng._bind.lower(
            eng.state, i32(0), jnp.zeros((1, setup[1].vocab)), i32(8),
            jnp.float32(0), i32(4), jnp.full((4,), -1, jnp.int32),
            eng._next_key()),
    }
    module = {k: v.as_text().split("\n")[0] for k, v in lowered.items()}
    assert "@jit_decode_fn " in module["serve.decode"]
    assert "lambda" in module["serve.chunk_mid"]
    assert "lambda" in module["serve.chunk_last"]
    for scope, low in lowered.items():
        text = low.as_text(debug_info=True)
        assert f"/{scope}/" in text, scope
        for pattern in ("decode_fn", "lambda", "_paged_attention_call",
                        "_chunk_attention_call", "led_matmul"):
            assert pattern not in scope
