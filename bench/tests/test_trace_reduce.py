"""The reduction from a trace to per-layer numbers.

``bench/testdata/trace_small.json`` is written by hand in the form a TPU
trace takes (ops named by their whole HLO instruction, a runtime plane
beside the chip's): a 1 ms window, three engine steps (the last one runs
past the window's end), a decode program with a paged-attention kernel,
a prefill chunk program with a chunk-attention kernel, and a bind program
outside every step.  ``bench/testdata/trace_tpu_excerpt.json`` is the
first 50 ms of a traced ``qwen2.5-3b.longctx`` run on one TPU v5e."""

import json

import pytest

import costs
import trace_reduce as tr
import work
from conftest import BENCH, ROOT

TRACE = tr.load_json(BENCH / "testdata" / "trace_small.json")
CONF = json.loads((BENCH / "configs" / "qwen2.5-3b.json").read_text())
LED = json.loads((BENCH / "configs" / "qwen2.5-3b-led-r0.5.json").read_text())
K = 1000.0


def test_window_and_planes():
    assert tr.window(TRACE) == (0.0, 1000 * K)
    assert [p["name"] for p in tr.device_planes(TRACE)] == ["/device:TPU:0"]


def test_busy_union_and_idle_share():
    plane = tr.device_planes(TRACE)[0]
    # ops [120, 320] + [520, 820] + [950, 970] us; the op at 1000 us lies
    # past the window
    assert tr.busy_ns(plane, 0, 1000 * K) == pytest.approx(520 * K)
    assert tr.union([(0, 5), (3, 9), (12, 15), (14, 20)], 1, 18) == [
        [1, 9], [12, 18]]


def test_idle_gaps_by_host_span():
    plane = tr.device_planes(TRACE)[0]
    gaps = dict(tr.idle_gaps(TRACE, plane, 0, 1000 * K))
    # [0, 120]: mid 60 us, no span; [320, 520]: mid 420, none open;
    # [820, 950]: mid 885, inside the second step; [970, 1000]: mid 985,
    # no span
    assert gaps["bench.engine_step"] == pytest.approx(130e-6)
    assert gaps["host: outside bench spans"] == pytest.approx(350e-6)
    assert sum(gaps.values()) == pytest.approx(480e-6)


def test_per_kernel_and_per_program_sums_within_steps():
    ops = tr.step_events(TRACE, tr.OPS_LINE, work.PAGED)
    assert [tr.op_name(e) for e in ops] == ["_paged_attention_call.3"]
    assert sum(e[2] for e in ops) == 100 * K
    chunk = tr.step_events(TRACE, tr.OPS_LINE, work.CHUNK_ATTN)
    assert sum(e[2] for e in chunk) == 150 * K
    # only the two steps wholly inside the window count; the bind program
    # ran outside them, the last decode after the window
    assert tr.step_spans(TRACE) == [(100 * K, 400 * K), (500 * K, 900 * K)]
    dec = tr.step_events(TRACE, tr.MODULES_LINE, work.DECODE)
    assert [e[2] for e in dec] == [200 * K]
    assert tr.step_events(TRACE, tr.MODULES_LINE, work.CHUNK)[0][2] == 300 * K


def test_top_ops_names_kernels_by_wrapper():
    plane = tr.device_planes(TRACE)[0]
    top = dict(tr.top_ops(plane, 0, 1000 * K))
    assert top["_chunk_attention_call"] == pytest.approx(150e-6)
    assert top["_paged_attention_call"] == pytest.approx(100e-6)
    assert top["fusion"] == pytest.approx(270e-6)
    assert tr.op_name(["%copy_bitcast_fusion.4 = bf16[2] fusion(%a)", 0, 1,
                       ""]) == "copy_bitcast_fusion.4"


def test_roofline_arithmetic():
    peak = tr.peaks("TPU v5 lite")
    assert peak == {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9}
    # memory bound: 819 MB in 2 ms is half the roofline
    assert tr.roofline_share(1e6, 819e6, 2e-3, peak) == pytest.approx(50.0)
    # compute bound: 197 GFLOP in 1 ms
    assert tr.roofline_share(197e9, 1.0, 1e-3, peak) == pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        tr.peaks("TPU v9 imaginary")


def test_costs_from_the_configuration():
    # qwen2.5-3b: 77.1 M projection weights a layer; LED 49.2 M
    dense = costs.matmul_flops_per_token(CONF) / 2 / 36
    led = costs.matmul_flops_per_token(LED) / 2 / 36
    assert dense == 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert led == 2 * (2048 * 1024 + 1024 * 2048) + 2 * (2048 * 128 + 128 * 256) \
        + 3 * (2048 * 1024 + 1024 * 11008)
    # one slot at 1000 keys: K and V of 2 heads x 128, bf16, per layer
    flops, nbytes = costs.paged_attention_cost(CONF, [1000])
    assert flops == 4 * 36 * 16 * 128 * 1000
    assert nbytes == 36 * (2 * 16 * 128 * 2 + 1000 * 2 * 2 * 128 * 2)
    assert costs.chunk_keys(10, 3) == 11 + 12 + 13


def test_reader_of_the_recorded_trace():
    """The per-layer readers on the small trace: the work recorded for
    its two steps against their device time."""
    import types

    run = types.SimpleNamespace(
        conf=CONF, device_trace=TRACE, peak=tr.peaks("TPU v5 lite"),
        work={"steps": [([100, 300], []),
                                    ([], [(0, 256, False)])]})
    from importlib import import_module

    paged = import_module("metrics.paged_attention_roofline").read(run)
    f, b = costs.paged_attention_cost(CONF, [100, 300])
    assert paged == pytest.approx(100 * b / 819e9 / 100e-6)
    assert import_module("metrics.decode_step_ms").read(run) == pytest.approx(0.2)
    ms = import_module("metrics.prefill_ms_per_ktok").read(run)
    assert ms == pytest.approx(0.3 / 0.256)
    assert import_module("metrics.idle_share").read(run) == pytest.approx(
        48.0)
    led_ms = import_module("metrics.led_matmul_ms")
    assert led_ms.read(run) is None     # a dense model: no LED calls
    # the LED model, one led_matmul call of 10 us in the first step
    trace = json.loads(json.dumps(TRACE))
    ops = [ln for ln in tr.device_planes(trace)[0]["lines"]
           if ln["name"] == tr.OPS_LINE][0]
    ops["events"].append(["%led_matmul_2d.1 = bf16[32,2048] custom-call()",
                          130 * K, 10 * K, "jit(decode_fn)/led_matmul_2d"])
    led_run = types.SimpleNamespace(**dict(vars(run), conf=LED,
                                           device_trace=trace))
    assert led_ms.read(led_run) == pytest.approx(0.010 / 2)
    mfu = import_module("metrics.mfu").read(run)
    want = (costs.decode_step_flops(CONF, [100, 300])
            + costs.chunk_flops(CONF, 0, 256, False)) / (500e-6 * 197e12)
    assert mfu == pytest.approx(100 * want)
    assert ROOT.is_dir()


def test_recorded_tpu_excerpt():
    t = tr.load_json(BENCH / "testdata" / "trace_tpu_excerpt.json")
    planes = tr.device_planes(t)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    lo, _ = tr.window(t)
    hi = lo + 0.05e9  # the excerpt's end
    busy = tr.busy_ns(planes[0], lo, hi) / (hi - lo)
    assert 0.9 < busy <= 1.0
    top = dict(tr.top_ops(planes[0], lo, hi))
    assert "_chunk_attention_call" in top and "copy" in top
    assert all(len(k) < 64 for k in top)
    chunk = [e for e in tr.line_events(planes[0], tr.OPS_LINE)
             if tr.matches(e, work.CHUNK_ATTN)]
    assert chunk and all(tr.op_name(e).startswith("_chunk_attention_call")
                         for e in chunk)
