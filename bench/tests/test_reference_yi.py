"""The llama reference (``bench/reference_yi.py``) and the readers of a
cell served over a mesh of chips (``bench/mesh_work.py``).

At a reduced size on the CPU: the program's cached prefill-then-decode
gives the reference's logits for an untied llama decoder; the weights the
reference makes sharded over four emulated devices are the arrays it
makes on one; and each new reader, on a synthetic trace with four device
planes, divides the model-wide work by the chips that share each layer."""

import json
import os
import subprocess
import sys
import textwrap
import types
from importlib import import_module

import jax.numpy as jnp
import numpy as np
import pytest

import mesh_work
import program
import reference_yi
import trace_reduce as tr
from conftest import BENCH, ROOT

TINY = json.loads((BENCH / "testdata" / "tiny_yi.json").read_text())
YI = json.loads((BENCH / "configs" / "yi-9b.json").read_text())
K = 1000.0


def tiny(dtype: str = "float32", model: int = 1) -> dict:
    """The tiny llama configuration on ``model`` devices of this process."""
    conf = json.loads(json.dumps(TINY))
    conf["torch_dtype"] = dtype
    conf["program"]["replace"]["dtype"] = dtype
    conf["deployment"]["mesh"] = {"data": 1, "model": model}
    return conf


def test_prefill_then_cached_decode_matches_reference():
    conf = tiny()
    weights = reference_yi.make_weights(conf, 2**32 + 19)
    cfg = program.arch_config(conf)
    assert not cfg.tie_embeddings and not cfg.qkv_bias
    assert cfg.n_heads // cfg.n_kv_heads == 8
    model = program.load_model(conf, cfg, weights)
    tokens = np.random.default_rng(0).integers(0, conf["vocab_size"], 24)
    n_prompt = 13
    want = np.asarray(reference_yi.logits(conf, weights, tokens))

    cache = model.init_cache(1, len(tokens), cfg, dtype=jnp.float32)
    got, cache = model.prefill(jnp.asarray(tokens[None, :n_prompt]), cache)
    rows = [np.asarray(got)[0, -1]]
    for t in tokens[n_prompt:-1]:
        got, cache = model.decode(jnp.asarray([[t]]), cache)
        rows.append(np.asarray(got)[0, -1])
    got = np.stack(rows)
    ref = want[n_prompt - 1:-1]
    scale = np.abs(ref).max()
    assert scale > 0.1
    np.testing.assert_allclose(got, ref, atol=1e-4 * scale, rtol=0)


def test_head_is_untied_and_rope_theta_comes_from_the_file():
    conf = tiny()
    weights = reference_yi.make_weights(conf, 3)
    tokens = np.random.default_rng(1).integers(0, conf["vocab_size"], 16)
    full = np.asarray(reference_yi.logits(conf, weights, tokens))
    tied = dict(weights, lm_head=weights["embed"].T)
    assert not np.allclose(
        full, np.asarray(reference_yi.logits(conf, tied, tokens)))
    other = dict(conf, rope_theta=1e6)
    assert not np.allclose(
        full, np.asarray(reference_yi.logits(other, weights, tokens)))


def test_scores_agree_with_logits():
    conf = tiny()
    weights = reference_yi.make_weights(conf, 3)
    tokens = np.random.default_rng(1).integers(0, conf["vocab_size"], 16)
    full = np.asarray(reference_yi.logits(conf, weights, tokens))
    best, target, top = reference_yi.scores(conf, weights, tokens, block=8,
                                            pad_to=8)
    np.testing.assert_allclose(best, full[:-1].max(-1), rtol=1e-5)
    np.testing.assert_array_equal(top, full[:-1].argmax(-1))
    np.testing.assert_allclose(
        target, full[np.arange(15), tokens[1:]], rtol=1e-5)
    # the default pads to the context; every real position is left alone
    best2, _, _ = reference_yi.scores(conf, weights, tokens, block=8)
    np.testing.assert_allclose(best2, best, rtol=1e-5)


def test_control_is_a_lower_precision():
    conf = tiny("bfloat16")
    weights = reference_yi.make_weights(conf, 4)
    tokens = np.random.default_rng(2).integers(0, conf["vocab_size"], 64)
    exact = reference_yi.served_gaps(conf, weights, tokens[:32],
                                     tokens[32:], block=8, pad_to=64)
    ctrl = reference_yi.served_gaps(conf, weights, tokens[:32], tokens[32:],
                                    quant="fp8", block=8, pad_to=64)
    assert exact.shape == ctrl.shape == (32,)
    assert (ctrl >= 0).all() and ctrl.max() > 0


def test_placement_is_the_programs_megatron_layout():
    specs = reference_yi.weight_specs(YI)
    d = reference_yi.dims(YI)
    assert (d["model"], d["data"], d["tied"]) == (4, 1, False)
    assert specs["lm_head"][0] == (4096, 64000)
    program.import_program()
    from repro.dist.sharding import spec_for_param

    class Mesh4:
        shape = {"data": 1, "model": 4}

    for leaf, name in YI["program"]["weights"].items():
        shape, _, spec = specs[name]
        assert spec == spec_for_param(leaf, shape, Mesh4), leaf


SHARDED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, sys.argv[1])
    import jax
    import numpy as np
    import reference_yi

    conf = json.loads(open(sys.argv[2]).read())
    conf["deployment"]["mesh"] = {"data": 1, "model": 4}
    sharded = reference_yi.make_weights(conf, 2**33 + 5)
    one = json.loads(json.dumps(conf))
    one["deployment"]["mesh"] = {"data": 1, "model": 1}
    whole = reference_yi.make_weights(one, 2**33 + 5)
    for name, a in sharded.items():
        assert len(a.sharding.device_set) == 4, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(whole[name]))
        shard = a.addressable_shards[0].data.shape
        if name in ("embed", "lm_head", "q_proj", "o_proj", "down_proj"):
            assert np.prod(shard) * 4 == a.size, (name, shard)
    print("SHARDED_WEIGHTS_OK")
""")


def test_sharded_weights_equal_the_unsharded_ones():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, "-c", SHARDED, str(BENCH),
         str(BENCH / "testdata" / "tiny_yi.json")],
        capture_output=True, text=True, env=env, timeout=300)
    assert "SHARDED_WEIGHTS_OK" in r.stdout, r.stderr[-3000:]


# -- the readers of a cell on four chips --------------------------------------


def four_chip_trace() -> dict:
    """A 1 ms window, two engine steps wholly inside it: a decode step
    (program 200 us on chip 0, 210 on the others; a paged-attention
    kernel of 40 us and two all-reduces of 5 and 7 us on each chip, a
    third all-reduce outside the program) and a prefill-chunk step
    (program 300 us, a chunk-attention kernel of 60 us on chip 0 and 80
    on the others, an all-gather of 10 us)."""
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0.0, 1000 * K, ""],
        ["bench.engine_step", 100 * K, 300 * K, ""],
        ["serve.step", 100 * K, 300 * K, ""],
        ["bench.engine_step", 500 * K, 400 * K, ""],
        ["serve.step", 500 * K, 400 * K, ""]]}]}
    planes = [host]
    for chip in range(4):
        dec = 200 * K if chip == 0 else 210 * K
        chunk_k = 60 * K if chip == 0 else 80 * K
        modules = [["jit_decode_fn(1)", 120 * K, dec, ""],
                   ["jit__lambda_(2)", 520 * K, 300 * K, ""]]
        ops = [
            ["%fusion.1 = bf16[4096]{0} fusion(%p.1)", 120 * K, 50 * K, ""],
            ["%_paged_attention_call.3 = bf16[32,1,8,128]{3,2,1,0} "
             "custom-call(%p.2)", 170 * K, 40 * K, ""],
            ["%all-reduce.4 = bf16[32,1,4096]{2,1,0} all-reduce(%f.1)",
             210 * K, 5 * K, ""],
            ["%all-reduce.5 = bf16[32,1,4096]{2,1,0} all-reduce(%f.2)",
             220 * K, 7 * K, ""],
            ["%fusion.2 = bf16[4096]{0} fusion(%p.1)", 520 * K, 100 * K, ""],
            ["%_chunk_attention_call.9 = bf16[1,2048,128]{2,1,0} "
             "custom-call(%p.3)", 620 * K, chunk_k, ""],
            ["%all-gather.6 = bf16[256,4096]{1,0} all-gather(%f.3)",
             700 * K, 10 * K, ""],
            ["%all-reduce.7 = bf16[2]{0} all-reduce(%f.4)", 950 * K, 3 * K,
             ""]]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": tr.MODULES_LINE, "events": modules},
            {"name": tr.OPS_LINE, "events": ops}]})
    return {"planes": planes}


def four_chip_run(conf=YI):
    return types.SimpleNamespace(
        conf=conf, ref=reference_yi, cell={"chips": 4},
        device_trace=four_chip_trace(), peak=tr.peaks("TPU v5 lite"),
        work={"steps": [([1000, 3000], []), ([], [(1024, 256, True)])]})


def read(name, run):
    return import_module(f"metrics.{name}").read(run)


def test_collectives_per_decode_step_on_every_chip():
    run = four_chip_run()
    # 12 us on every chip in its one decode; the all-gather sits in the
    # chunk program, the last all-reduce outside every program
    assert read("collective_ms", run) == pytest.approx(0.012)
    one = four_chip_run()
    for plane in tr.device_planes(one.device_trace):
        ops = tr.line_events(plane, tr.OPS_LINE)
        ops[:] = [e for e in ops if not mesh_work.is_collective(e)]
    assert read("collective_ms", one) is None


def test_shard_rooflines_divide_the_work_by_the_model_axis():
    run = four_chip_run()
    peak = tr.peaks("TPU v5 lite")
    # decode: K and V of 4 heads x 128 for 4000 keys, q in and out, 48
    # layers, bf16; a quarter of it on each chip, over the 40 us kernel
    nbytes = 48 * (2 * 2 * 32 * 128 * 2 + 4000 * 2 * 4 * 128 * 2)
    assert mesh_work.paged_attention_cost(run)[1] == nbytes
    got = read("shard_paged_attention_roofline", run)
    assert got == pytest.approx(100 * nbytes / 4 / peak["hbm_bytes_s"] / 40e-6)
    # chunk: 256 queries at 1024; the kernel averages 75 us over the chips
    flops = 4 * 48 * 32 * 128 * (256 * 1024 + 256 * 257 // 2)
    cbytes = 48 * (2 * 256 * 32 * 128 * 2 + 1280 * 2 * 4 * 128 * 2)
    assert mesh_work.chunk_attention_cost(run) == (flops, cbytes)
    least = max(flops / 4 / peak["bf16_flops"],
                cbytes / 4 / peak["hbm_bytes_s"])
    assert read("shard_chunk_attention_roofline", run) == pytest.approx(
        100 * least / 75e-6)


def test_mesh_mfu_is_the_whole_steps_share_of_four_peaks():
    run = four_chip_run()
    d = reference_yi.dims(YI)
    per_token = 2 * 4096 * (2 * 4096 + 2 * 512 + 3 * 11008) * 48
    head = 2 * 4096 * 64000
    assert mesh_work.token_flops(d) == per_token + head
    flops = (2 * (per_token + head) + 4 * 48 * 32 * 128 * 4000
             + 256 * per_token + head
             + 4 * 48 * 32 * 128 * (256 * 1024 + 256 * 257 // 2))
    assert mesh_work.step_flops(run) == flops
    seconds = (200e-6 + 3 * 210e-6) / 4 + 300e-6
    assert read("mesh_mfu", run) == pytest.approx(
        100 * flops / (seconds * 197e12 * 4))


def test_the_one_chip_readers_read_the_first_chip():
    run = four_chip_run()
    assert read("decode_step_ms", run) == pytest.approx(0.2)
    # chip 0 is idle 20 + 5 + 173 us of the first step and 20 + 20 + 190
    # of the second
    assert read("step_idle_ms", run) == pytest.approx((198 + 230) / 2e3)
