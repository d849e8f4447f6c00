"""The readers of the program's own telemetry: the counters of
``prefill_stats()`` (queue and prefill wait, mixed steps, compiles) and
the device idle inside the program's ``serve.step`` spans
(``bench/program_spans.py``).

``bench/testdata/trace_serve_spans.json`` is an excerpt of a traced
``qwen2.5-3b.chat`` run on one TPU v5e: the host spans of the
benchmark's window and of whole engine steps, and the device's programs
(the "XLA Modules" line; the ops are left out for size)."""

import types
from importlib import import_module

import pytest

import program_spans as ps
import run as bench_run
import trace_reduce as tr
from conftest import BENCH, TINY_CELL, tiny_benchmark

RECORDED = tr.load_json(BENCH / "testdata" / "trace_serve_spans.json")
BENCH_SPANS_ONLY = tr.load_json(BENCH / "testdata" / "trace_small.json")
COUNTERS = ("queue_wait_p95_ms", "prefill_wait_p95_ms", "mixed_step_share",
            "window_compiles")
READERS = COUNTERS + ("step_idle_ms",)
K = 1000.0


def reader(name):
    return import_module(f"metrics.{name}")


def _trace(host, ops):
    """A trace in the plain form: host spans ``(name, start_us, end_us)``
    on one thread, device ops ``(start_us, end_us)``."""
    ev = lambda n, s, e: [n, s * K, (e - s) * K, ""]  # noqa: E731
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [ev(*h) for h in host]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE,
             "events": [ev("%fusion.1 = f32[2] fusion()", s, e)
                        for s, e in ops]}]}]}


SYNTHETIC = _trace(
    host=[("bench.window", 0, 1000),
          ("serve.step", 100, 400), ("serve.admit", 100, 150),
          ("serve.decode", 200, 220), ("serve.host_sync", 220, 395),
          ("serve.fanout", 410, 480),
          ("serve.step", 500, 900), ("serve.emit", 885, 900),
          ("serve.step", 950, 1050)],     # runs past the window
    ops=[(160, 210), (240, 380), (520, 880), (960, 1040)])


def test_step_idle_splits_by_the_innermost_phase():
    # step 1: idle [100, 160] under admit, [210, 240] and [380, 400]
    # under host_sync; step 2: [500, 520] in no phase, [880, 900] under
    # emit; the third step lies past the window, fanout outside steps
    split = ps.step_idle(SYNTHETIC)
    assert split["steps"] == 2
    assert split["idle_ms_per_step"] == pytest.approx(0.150 / 2)
    assert split["by_phase"] == pytest.approx({
        "serve.admit": 0.030, "serve.host_sync": 0.025,
        "serve.step": 0.010, "serve.emit": 0.010})
    assert reader("step_idle_ms").read(
        types.SimpleNamespace(device_trace=SYNTHETIC)) == pytest.approx(0.075)


def test_step_idle_of_the_recorded_trace():
    split = ps.step_idle(RECORDED)
    lo, hi = tr.window(RECORDED)
    steps = sorted((s, e) for n, s, e in ps.serve_spans(RECORDED)
                   if n == ps.STEP and lo <= s and e <= hi)
    assert split["steps"] == len(steps) >= 2
    plane = tr.device_planes(RECORDED)[0]
    ev = (tr.line_events(plane, tr.OPS_LINE)
          or tr.line_events(plane, tr.MODULES_LINE))
    idle_ns = sum((e - s) - sum(b - a for a, b in tr.union(
        ((x[1], x[1] + x[2]) for x in ev), s, e)) for s, e in steps)
    assert split["idle_ms_per_step"] == pytest.approx(
        idle_ns * 1e-6 / len(steps))
    assert sum(split["by_phase"].values()) == pytest.approx(
        split["idle_ms_per_step"])
    assert set(split["by_phase"]) <= {
        "serve.step", "serve.admit", "serve.table_flush",
        "serve.prefill_chunk", "serve.bind", "serve.decode",
        "serve.host_sync", "serve.emit"}
    mean_step_ms = sum(e - s for s, e in steps) * 1e-6 / len(steps)
    assert 0 < split["idle_ms_per_step"] < mean_step_ms
    # on the chip the host waits out most of the idle in serve.host_sync
    assert max(split["by_phase"], key=split["by_phase"].get) \
        == "serve.host_sync"


def test_counter_readers():
    run = types.SimpleNamespace(counters={
        "queue_wait_p95_s": 0.25, "prefill_wait_p95_s": 3.5,
        "decode_steps": 140, "mixed_steps": 56, "programs_compiled": 0})
    got = {n: reader(n).read(run) for n in COUNTERS}
    assert got == pytest.approx({
        "queue_wait_p95_ms": 250.0, "prefill_wait_p95_ms": 3500.0,
        "mixed_step_share": 40.0, "window_compiles": 0})
    assert got["window_compiles"] is not None   # 0 is a reading
    run.counters["decode_steps"] = 0
    assert reader("mixed_step_share").read(run) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_the_program_telemetry(name):
    """A program that keeps none of these counters and opens no
    ``serve.*`` span, as before they were added."""
    older = {"prompt_tokens_admitted": 300, "prefix_skipped_tokens": 200,
             "prefill_chunks": 4}
    for trace in (BENCH_SPANS_ONLY, {}):
        run = types.SimpleNamespace(counters=older, device_trace=trace)
        assert reader(name).read(run) is None
    no_window = _trace([("serve.step", 100, 400)], [(160, 210)])
    assert ps.step_idle(no_window) is None
    assert ps.step_idle({"planes": SYNTHETIC["planes"][:1]}) is None


def test_new_metrics_are_reported_by_the_chat_cells():
    spec = tiny_benchmark()
    cell = spec["workloads"][0]
    names = {m["name"] for m in bench_run.cell_metrics(spec, cell, True)}
    assert {f"{n}.chat" for n in READERS} <= names


def test_readers_on_the_tiny_cell(checkout):
    _, run, _ = bench_run.start(TINY_CELL, 2**33 + 7, 4.0, False,
                                bench_dir=checkout, require_tpu=False)
    _, _, engine = bench_run.build(run)
    bench_run.measure(run, engine)
    got = {n: reader(n).read(run) for n in READERS}
    assert got["window_compiles"] == 0
    assert 0 < got["mixed_step_share"] < 100
    assert got["queue_wait_p95_ms"] >= 0 and got["prefill_wait_p95_ms"] > 0
    assert got["step_idle_ms"] is None     # untraced: no device trace
