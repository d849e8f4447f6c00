"""Smoke run of the serving path on a TPU, at qwen2.5-3b's full width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # {data, model} mesh 2x2 vs one chip

Drives the path a user drives, with random weights from a seed: the model
built from its published config (``repro.configs.qwen2_5_3b``: 36 layers,
d_model 2048, 16 query heads over 2 KV heads of 128, bf16),
``ContinuousEngine`` with the Pallas kernels and a paged KV pool, the HTTP
front door (``repro.serve.http.BackgroundServer``) driven by the
``repro.launch.loadgen`` client, then the same trace through the model
after the paper's post-training factorization (``auto_fact``, SVD on the
chip, rank 0.5), whose LED layers run the ``led_matmul`` kernel.

One chip, in order: device check; kernel parity at the model's shapes
against the ``repro.kernels.ref`` oracles; the dense model over HTTP; the
factorized model over HTTP.  ``--four-chips`` runs only the dense trace on
a 2x2 ``{data, model}`` mesh (reference kernels: the Pallas kernels are
single-shard) and the same trace on one chip, for comparison.

A failed gate raises, so the script exits non-zero; the last line of
standard output is one JSON object naming the device, printed only when
every phase passed.  Off the TPU it exits non-zero before any phase runs.
Times printed are host-clock seconds on the chip, compile included where
labelled; they are a smoke run's, not a benchmark's.

Everything runs in this one process, because a chip belongs to one
process: the loadgen client runs on an asyncio loop here, not in a child.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import functools
import gc
import json
import sys
import time
from importlib.metadata import PackageNotFoundError, version
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import auto_fact  # noqa: E402
from repro.dist.runtime import make_serve_mesh  # noqa: E402
from repro.kernels import (chunk_attention, chunk_attention_ref,  # noqa: E402
                           led_matmul, led_matmul_ref, paged_attention,
                           paged_attention_ref)
from repro.launch import loadgen  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.hlo_analysis import pallas_kernels  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import ContinuousEngine, generate  # noqa: E402
from repro.serve.http import BackgroundServer  # noqa: E402

PLATFORM = "tpu"  # the only platform any phase may run on
ARCH = "qwen2.5-3b"
RANK = 0.5  # auto_fact rank, a ratio of min(m, n) per layer
SEED = 0
REQUEST_TIMEOUT_S = 600.0

# Kernel parity tolerance, on max|kernel - oracle| / max|oracle|.  Every
# output is bf16 (the model dtype of q and x), for a float32 KV pool (the
# engine's default) and a bf16 one alike; the oracles run in float32 at
# highest matmul precision.  The kernels differ from them by accumulation
# order (online softmax), by MXU passes over float32 operands (2^-9
# relative per product) and by the final bf16 rounding (2^-8 relative).
# Four bf16 ulps of the largest output cover all three; a masking,
# layout or tiling fault gives errors of order one.
TOL = 2.0 ** -6


class SmokeFailure(RuntimeError):
    """A gate of the smoke run failed."""


@dataclasses.dataclass(frozen=True)
class Dims:
    """Serving shape: engine slots and context, KV block and prefill chunk
    widths, and the trace (prompts share one system prefix)."""

    slots: int = 8
    max_len: int = 2048
    block: int = 16
    chunk: int = 256
    prefix: int = 64
    min_prompt: int = 128
    max_prompt: int = 1024
    max_new: int = 32
    requests: int = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device() -> dict:
    """The device JAX found, as the last line reports it; raises off
    :data:`PLATFORM`."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM:
        raise SmokeFailure(
            f"no {PLATFORM} device: JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); this smoke run never falls back")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory(devices) -> str:
    """Peak and current device memory, as the backend reports them."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            out.append(f"device {d.id}: not reported")
            continue
        out.append(f"device {d.id}: peak "
                   f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB, "
                   f"in use {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB")
    return "; ".join(out)


# -- phase: kernel parity -----------------------------------------------------


def _parity(name: str, kernel, oracle, args, valid_rows=None) -> float:
    fn = jax.jit(kernel)
    t0 = time.monotonic()
    jax.block_until_ready(fn(*args))
    first_s = time.monotonic() - t0
    t0 = time.monotonic()
    y = jax.block_until_ready(fn(*args))
    run_s = time.monotonic() - t0
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(oracle)(*args)
    y, ref = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    if not np.isfinite(y).all():
        raise SmokeFailure(f"{name}: non-finite kernel output")
    if valid_rows is not None:  # padding rows carry no contract
        y, ref = y[:valid_rows], ref[:valid_rows]
    err = float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))
    log(f"  {name:34s} err {err:.3e} (tol {TOL:.3e})  first call "
        f"{first_s:.2f} s (compile included), second {run_s * 1e3:.3f} ms")
    if not err <= TOL:
        raise SmokeFailure(f"{name}: kernel vs oracle error {err:.3e} > "
                           f"{TOL:.3e}")
    return err


def kernel_parity(cfg, dims: Dims, seed: int = SEED) -> dict:
    """``paged_attention``, ``chunk_attention`` (float32 and bf16 pools) and
    ``led_matmul`` (decode and prefill rows) against their oracles at the
    config's head, block and projection shapes."""
    heads, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    n_table = dims.max_len // dims.block
    n_blocks = dims.slots * n_table
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    # the engine's layout: ragged positions, distinct blocks per slot up
    # to each position, sentinel (n_blocks) past it
    pos = rng.integers(dims.block, dims.max_len - dims.chunk,
                       dims.slots).astype(np.int32)
    table = np.full((dims.slots, n_table), n_blocks, np.int32)
    perm, used = rng.permutation(n_blocks), 0
    for b, p in enumerate(pos):
        need = p // dims.block + 1
        table[b, :need] = perm[used:used + need]
        used += need
    table, jpos = jnp.asarray(table), jnp.asarray(pos)
    # slot 0's chunk resumes mid-block after its resident prefix, with
    # three padding rows
    offset, n_valid = jnp.int32(pos[0]), dims.chunk - 3
    q = jax.random.normal(next(keys), (dims.slots, heads, hd), dt)
    qc = jax.random.normal(next(keys), (dims.chunk, heads, hd), dt)
    errs = {}
    for pool_dt in (jnp.float32, jnp.bfloat16):
        tag = jnp.dtype(pool_dt).name
        pool = (kvh, n_blocks, dims.block, hd)
        kp = jax.random.normal(next(keys), pool, pool_dt)
        vp = jax.random.normal(next(keys), pool, pool_dt)
        kc = jax.random.normal(next(keys), (dims.chunk, kvh, hd), pool_dt)
        vc = jax.random.normal(next(keys), (dims.chunk, kvh, hd), pool_dt)
        errs[f"paged_attention/{tag}"] = _parity(
            f"paged_attention ({tag} pool)", paged_attention,
            paged_attention_ref, (q, kp, vp, table, jpos))
        errs[f"chunk_attention/{tag}"] = _parity(
            f"chunk_attention ({tag} pool)", chunk_attention,
            chunk_attention_ref,
            (qc, kp, vp, table[0], kc, vc, offset, jnp.int32(n_valid)),
            valid_rows=n_valid)
    rank = int(RANK * min(cfg.d_model, cfg.d_ff))
    a = jax.random.normal(next(keys), (cfg.d_model, rank), dt) / np.sqrt(
        cfg.d_model)
    b = jax.random.normal(next(keys), (rank, cfg.d_ff), dt) / np.sqrt(rank)
    for role, rows in (("decode", dims.slots), ("prefill", dims.chunk)):
        x = jax.random.normal(next(keys), (rows, cfg.d_model), dt)
        errs[f"led_matmul/{role}"] = _parity(
            f"led_matmul ({role}, {rows} rows, r={rank})", led_matmul,
            led_matmul_ref, (x, a, b))
    return errs


# -- phase: serving over HTTP -------------------------------------------------


def make_payloads(cfg, dims: Dims, seed: int = SEED) -> list:
    """Request bodies: one shared system prefix, total prompt lengths
    spread over ``[min_prompt, max_prompt]`` (both ends included)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, dims.prefix)
    lengths = rng.integers(dims.min_prompt, dims.max_prompt + 1,
                           dims.requests)
    lengths[0], lengths[-1] = dims.min_prompt, dims.max_prompt
    return [{"prompt": np.concatenate(
                [prefix, rng.integers(0, cfg.vocab, n - dims.prefix)]
             ).tolist(),
             "max_new_tokens": dims.max_new} for n in lengths]


def step_programs(eng) -> tuple:
    """Compiled text of the engine's decode step and final prefill chunk,
    lowered with the arguments the engine passes them."""
    w = eng.buckets[-1]
    dst = np.full((w,), eng.manager.sentinel * eng.block_size, np.int32)
    decode = eng._decode.lower(eng._models, eng.cache, eng.state,
                               jax.random.PRNGKey(0)).compile()
    prefill = eng._chunk_last.lower(
        eng._models, eng._put_host(np.zeros((1, w), np.int32)), eng.cache,
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(w, jnp.int32), eng._put_host(dst)).compile()
    return decode.as_text(), prefill.as_text()


def serve_http(label: str, model, cfg, dims: Dims, payloads, *,
               kernel: str = "pallas", mesh=None) -> dict:
    """Serve ``payloads`` through the HTTP front door; gate on every
    request served, no errors and no KV block left in use."""
    eng = ContinuousEngine(
        model, cfg, batch=dims.slots, max_len=dims.max_len,
        max_prompt_len=dims.max_prompt, block_size=dims.block,
        chunk_size=dims.chunk, buckets=(dims.chunk,),
        decode_kernel=kernel, prefill_kernel=kernel, mesh=mesh)
    # one chunk and one token past it: the mid and final chunk programs,
    # the bind and the decode step compile here, not under the clients
    t0 = time.monotonic()
    eng.submit(np.zeros(dims.chunk + 1, np.int32), max_new_tokens=2)
    eng.run()
    compile_s = time.monotonic() - t0
    eng.reset_stats()
    with BackgroundServer(eng, max_pending=len(payloads)) as bg:
        t0 = time.monotonic()
        results = asyncio.run(loadgen.run_closed_loop(
            bg.host, bg.port, payloads, concurrency=dims.slots,
            timeout_s=REQUEST_TIMEOUT_S))
        run_s = time.monotonic() - t0
    summary = loadgen.summarize(results, run_s)
    kv = eng.kv_stats()
    prefill = eng.prefill_stats()
    log(f"  [{label}] served {summary['served']}/{len(payloads)}, errors "
        f"{summary['errors']}, {summary['streamed_tokens']} tokens streamed "
        f"in {run_s:.2f} s ({jax.devices()[0].platform}, host clock); "
        f"warm-up (compile included) {compile_s:.1f} s")
    log(f"  [{label}] prefix-hit tokens {prefill['prefix_skipped_tokens']}"
        f"/{prefill['prompt_tokens_admitted']}, peak blocks "
        f"{kv['peak_blocks_in_use']}/{kv['n_blocks']}, blocks in use after "
        f"drain {kv['blocks_in_use']}")
    if summary["errors"] or summary["served"] != len(payloads):
        raise SmokeFailure(f"{label}: {summary['served']}/{len(payloads)} "
                           f"served, {summary['errors']} errors")
    if kv["blocks_in_use"] or not eng.scheduler.idle:
        raise SmokeFailure(f"{label}: {kv['blocks_in_use']} KV blocks "
                           "leaked after the trace drained")
    texts = step_programs(eng) if mesh is None else None
    return {"tokens": [r["tokens"] for r in results], "programs": texts,
            "compile_s": compile_s, "run_s": run_s}


def require_kernels(label: str, programs, expected: dict) -> None:
    """Gate: each compiled step program holds the expected Pallas
    kernels."""
    for (step, want), text in zip(expected.items(), programs):
        found = pallas_kernels(text)
        log(f"  [{label}] compiled {step} step: Pallas kernels "
            f"{sorted(found) or 'none'}")
        if not set(want) <= found:
            raise SmokeFailure(f"{label}: compiled {step} step lacks "
                               f"{sorted(set(want) - found)}")


# -- phase: reference tokens --------------------------------------------------


@functools.partial(jax.jit, static_argnames="n")
def _one_shot(model, tokens, cache, n):
    logits, _ = model.prefill(tokens, cache)
    toks, _ = generate(model, tokens, cache, n_steps=n)
    return jnp.isfinite(logits).all(), toks[0]


def reference_agreement(model, cfg, dims: Dims, payloads, served) -> float:
    """One-shot ``generate`` for the trace's first request: gates finite
    prefill logits, returns greedy-token agreement with the engine
    (random weights give near-tied logits, so agreement is reported, not
    gated)."""
    prompt = np.asarray(payloads[0]["prompt"], np.int32)
    n = dims.max_new
    cache = model.init_cache(1, len(prompt) + n, cfg, dtype=jnp.float32)
    t0 = time.monotonic()
    finite, toks = _one_shot(model, jnp.asarray(prompt)[None], cache, n=n)
    toks = np.asarray(toks)
    log(f"  one-shot generate ({len(prompt)}-token prompt, {n} tokens): "
        f"{time.monotonic() - t0:.1f} s (compile included)")
    if not bool(finite):
        raise SmokeFailure("non-finite prefill logits")
    got = np.asarray(served[0][:n])
    return float((got == toks[:len(got)]).mean())


def agreement(a, b) -> float:
    pairs = [(x, y) for ta, tb in zip(a, b) for x, y in zip(ta, tb)]
    return float(np.mean([x == y for x, y in pairs])) if pairs else 0.0


# -- drivers ------------------------------------------------------------------


def build(cfg):
    t0 = time.monotonic()
    model = jax.jit(build_model, static_argnums=1)(jax.random.PRNGKey(SEED),
                                                   cfg)
    jax.block_until_ready(model)
    n = sum(x.size for x in jax.tree_util.tree_leaves(model))
    log(f"model {cfg.name}: {n / 1e9:.3f} B parameters ({cfg.dtype}), "
        f"built in {time.monotonic() - t0:.1f} s")
    return model


def one_chip(cfg, dims: Dims) -> None:
    devs = jax.devices()[:1]
    log("phase 1: kernel parity")
    kernel_parity(cfg, dims)
    log(f"  memory: {memory(devs)}")

    log("phase 2: dense model over HTTP (Pallas decode + prefill kernels)")
    model = build(cfg)
    payloads = make_payloads(cfg, dims)
    dense = serve_http("dense", model, cfg, dims, payloads)
    require_kernels("dense", dense["programs"],
                    {"decode": ["_paged_attention_call"],
                     "prefill": ["_chunk_attention_call"]})
    agree = reference_agreement(model, cfg, dims, payloads, dense["tokens"])
    log(f"  dense engine vs one-shot generate, request 0: {agree:.1%} "
        "greedy-token agreement (not gated)")
    log(f"  memory: {memory(devs)}")
    gc.collect()  # the dense engine's KV pool goes before the SVD

    log(f"phase 3: factorized model (auto_fact svd, rank {RANK}) over HTTP")
    t0 = time.monotonic()
    fact, report = auto_fact(model, RANK, solver="svd",
                             exclude=["embed", "lm_head"], gate=False,
                             return_report=True)
    jax.block_until_ready(fact)
    log(f"  auto_fact on {jax.devices()[0].platform}: {len(report.entries)} "
        f"layers, target params {report.params_before:,} -> "
        f"{report.params_after:,}, worst rel_err "
        f"{max(e[-1] for e in report.entries):.3f}, "
        f"{time.monotonic() - t0:.1f} s (compile included)")
    del model
    gc.collect()
    fact_out = serve_http("factorized", fact, cfg, dims, payloads)
    require_kernels("factorized", fact_out["programs"],
                    {"decode": ["_paged_attention_call", "led_matmul_2d"],
                     "prefill": ["_chunk_attention_call", "led_matmul_2d"]})
    log(f"  dense vs factorized: "
        f"{agreement(dense['tokens'], fact_out['tokens']):.1%} greedy-token "
        "agreement (random weights: not gated)")
    log(f"  memory: {memory(devs)}")


def four_chips(cfg, dims: Dims) -> None:
    mesh = make_serve_mesh("2,2")
    log("mesh {data: 2, model: 2} over the first four devices:")
    for (i, j), d in np.ndenumerate(mesh.devices):
        log(f"  data {i}, model {j}: device {d.id} "
            f"{getattr(d, 'coords', '')} {d.device_kind}")
    model = build(cfg)
    payloads = make_payloads(cfg, dims)
    log("phase 1: dense trace on the 2x2 mesh (reference kernels)")
    sharded = serve_http("mesh 2x2", model, cfg, dims, payloads,
                         kernel="reference", mesh=mesh)
    log(f"  memory: {memory(list(mesh.devices.flat))}")
    gc.collect()
    log("phase 2: the same trace on one device (reference kernels)")
    single = serve_http("one device", model, cfg, dims, payloads,
                        kernel="reference")
    log(f"  memory: {memory(jax.devices()[:1])}")
    log(f"  mesh vs one device: "
        f"{agreement(sharded['tokens'], single['tokens']):.1%} greedy-token "
        "agreement (bf16 reductions split across the model axis: not "
        "gated)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="serve the dense trace on a 2x2 {data, model} mesh "
                        "and on one chip; nothing else")
    args = p.parse_args(argv)
    try:
        device = check_device()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        print(f"FAIL: {need} {PLATFORM} devices needed, "
              f"{device['count']} found", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"device {device['kind']} x{device['count']}, jax {jax.__version__}, "
        f"libtpu {libtpu}, compile cache {cache_dir}")
    cfg = get_config(ARCH)
    try:
        (four_chips if args.four_chips else one_chip)(cfg, Dims())
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
