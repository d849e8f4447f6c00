"""Kernel microbenchmark harness: per-step decode + per-chunk prefill
timings with compilation separated from steady state, in the style of
maxtext's decode microbenchmark.

    PYTHONPATH=src python -m repro.launch.microbench --smoke \
        --history BENCH_history.jsonl

Every emitted **cell** is one JSON object stamped with explicit
provenance — ``compiled_backend`` (the backend the timing actually
compiled for, or ``null`` when the Pallas kernels ran in interpret
mode) and ``interpret_mode`` — so a 5x "slowdown" measured in
interpret mode on a CPU runner can never again masquerade as a real
perf number.  Cells append to ``BENCH_history.jsonl`` (one line each,
append-only) and ``benchmarks/check_regression.py`` gates the
trajectory against ``benchmarks/thresholds.json``: timing metrics are
compared only against prior cells with *matching* provenance, warn-only
off-TPU; correctness/count metrics hard-fail anywhere.

Four metric families, swept over (batch, seq, block_size, heads):

* ``decode_step_ms`` — one jitted model decode step against a fully
  resident paged cache, ``reference`` (dense gather) vs ``pallas``
  (fused :func:`repro.kernels.paged_attention`).
* ``prefill_chunk_ms`` — one jitted model prefill chunk mid-prompt,
  ``reference`` vs ``pallas`` (flash
  :func:`repro.kernels.chunk_attention`).
* ``kernel_us`` — the raw kernel calls (no model around them):
  ``paged_attention`` / ``chunk_attention``, each vs its jnp oracle.
* ``parity_max_abs_err`` — kernel-vs-oracle max abs error for both
  kernels (the correctness cells the regression gate hard-fails on).

``--sharded`` swaps the kernel matrix for the dp x tp serve grid
(:data:`SHARDED_GRID`): the reference decode / prefill-chunk steps with
params, paged pool, and activations placed on a ``{data, model}`` mesh
(variants ``sharded_dp{dp}tp{tp}``).  On a CPU host with fewer than 4
devices it re-execs itself under
``--xla_force_host_platform_device_count=8``; on an accelerator with
fewer than 4 devices it fails instead of falling back to the CPU.

Timing methodology: the first call (trace + compile + first run) is
recorded as ``compile_ms``, never mixed into steady state; ``warmup``
discarded iterations follow; then ``iters`` timed iterations with
``jax.block_until_ready`` per iteration give mean/p50/min.
``--profile-dir`` activates ``jax.profiler`` tracing around the timed
region of every variant (one trace subdir per cell key).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

SCHEMA = 1
SUITE = "microbench_kernels"


# ---------------------------------------------------------------------------
# provenance + cell plumbing
# ---------------------------------------------------------------------------


def provenance() -> dict:
    """The stamp every emitted cell carries.

    ``interpret_mode`` is the repo-wide Pallas policy
    (:func:`repro.kernels.ops.default_interpret`): True off-TPU, chosen
    by platform alone.  ``compiled_backend`` is the backend a
    kernel timing actually compiled for — ``None`` in interpret mode,
    because an interpreted timing measures the Pallas interpreter, not
    any hardware.  Two cells are comparable only when both fields (and
    the backend) match; see :func:`comparable`.
    """
    interp = _default_interpret()
    backend = jax.default_backend()
    dev = jax.devices()[0]
    return {
        "backend": backend,
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "compiled_backend": None if interp else backend,
        "interpret_mode": interp,
        "jax_version": jax.__version__,
    }


def _default_interpret() -> bool:
    from repro.kernels.ops import default_interpret

    return default_interpret()


def comparable(a: dict, b: dict) -> bool:
    """May two provenance stamps' timings be compared?  Same backend, same
    interpret mode, same compiled target — an interpret-mode CPU number
    vs a compiled TPU number is not a regression, it's a category error."""
    keys = ("backend", "interpret_mode", "compiled_backend")
    return all(a.get(k) == b.get(k) for k in keys)


def make_cell(metric: str, variant: str, axes: dict, stats: dict,
              prov: Optional[dict] = None, *, smoke: bool = False) -> dict:
    return {
        "schema": SCHEMA,
        "suite": SUITE,
        "metric": metric,
        "variant": variant,
        "axes": dict(axes),
        "stats": dict(stats),
        "provenance": dict(prov if prov is not None else provenance()),
        "smoke": smoke,
        "unix_time": time.time(),
    }


def cell_key(cell: dict) -> str:
    """Stable identity of a tracked series: metric/variant plus the sorted
    sweep axes.  ``check_regression`` groups history lines by this key
    (and by provenance) before comparing."""
    axes = "_".join(f"{k}{v}" for k, v in sorted(cell["axes"].items()))
    return f"{cell['metric']}/{cell['variant']}" + (f"@{axes}" if axes
                                                   else "")


def append_history(path: str, cells: Iterable[dict]) -> int:
    n = 0
    with open(path, "a") as fh:
        for cell in cells:
            fh.write(json.dumps(cell, sort_keys=True) + "\n")
            n += 1
    return n


@contextlib.contextmanager
def maybe_profile(trace_dir: Optional[str]):
    """Profiler-activation hook: wrap a timed region in a
    ``jax.profiler`` trace when a directory is given, no-op otherwise."""
    if not trace_dir:
        yield
        return
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# timing core
# ---------------------------------------------------------------------------


def time_fn(fn: Callable, *args, iters: int = 20, warmup: int = 3,
            profile_dir: Optional[str] = None) -> dict:
    """Time ``fn(*args)`` with compile/warmup separated from steady state.

    The first call (trace + compile + run) lands in ``compile_ms`` and
    never pollutes the steady-state stats; ``warmup`` further calls are
    discarded; then ``iters`` calls are timed individually with
    ``jax.block_until_ready`` each, giving mean/p50/min over real
    end-to-end step latencies.
    """
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    with maybe_profile(profile_dir):
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            samples.append((time.perf_counter() - t0) * 1e3)
    arr = np.asarray(samples)
    return {
        "mean_ms": float(arr.mean()),
        "p50_ms": float(np.percentile(arr, 50)),
        "min_ms": float(arr.min()),
        "std_ms": float(arr.std()),
        "compile_ms": compile_ms,
        "iters": iters,
        "warmup": warmup,
    }


# ---------------------------------------------------------------------------
# synthetic layouts (kernel-level cells need no model)
# ---------------------------------------------------------------------------


def _synthetic_paged(rng, *, batch, seq, block_size, heads, kvh, head_dim,
                     slack_blocks: int = 2):
    """A well-formed paged layout with every slot resident at ``seq``
    tokens: pool, per-slot tables (distinct blocks, sentinel tail), and
    per-slot positions."""
    n_table = -(-seq // block_size)
    n_blocks = batch * n_table + slack_blocks
    kp = jnp.asarray(rng.standard_normal(
        (kvh, n_blocks, block_size, head_dim)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(
        (kvh, n_blocks, block_size, head_dim)), jnp.float32)
    table = np.full((batch, n_table), n_blocks, np.int32)
    perm = rng.permutation(batch * n_table)
    table[:, :] = perm.reshape(batch, n_table)
    pos = np.full((batch,), seq - 1, np.int32)
    q = jnp.asarray(rng.standard_normal((batch, heads, head_dim)),
                    jnp.float32)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos)


def _synthetic_chunk(rng, *, seq, block_size, width, heads, kvh, head_dim):
    """One slot's mid-prompt chunk: resident prefix of ``seq - width``
    tokens behind a mapped table, plus ``width`` fresh chunk rows."""
    offset = max(seq - width, 0)
    n_table = -(-seq // block_size)
    n_blocks = n_table + 2
    kp = jnp.asarray(rng.standard_normal(
        (kvh, n_blocks, block_size, head_dim)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(
        (kvh, n_blocks, block_size, head_dim)), jnp.float32)
    table = jnp.asarray(rng.permutation(n_blocks)[:n_table], jnp.int32)
    q = jnp.asarray(rng.standard_normal((width, heads, head_dim)),
                    jnp.float32)
    kc = jnp.asarray(rng.standard_normal((width, kvh, head_dim)),
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((width, kvh, head_dim)),
                     jnp.float32)
    return (q, kp, vp, table, kc, vc, jnp.int32(offset), jnp.int32(width))


# ---------------------------------------------------------------------------
# the benchmarked paths
# ---------------------------------------------------------------------------


def bench_kernel_cells(point: dict, *, iters: int, warmup: int,
                       prov: dict, smoke: bool,
                       profile_dir: Optional[str] = None) -> list[dict]:
    """Raw-kernel cells for one sweep point: ``kernel_us`` timings for
    paged_attention / chunk_attention (kernel + oracle each) plus the
    ``parity_max_abs_err`` correctness cells."""
    from repro.kernels import (chunk_attention, chunk_attention_ref,
                               paged_attention, paged_attention_ref)

    rng = np.random.default_rng(0)
    axes = dict(point)
    kvh = max(1, point["heads"] // 2)
    dims = dict(batch=point["batch"], seq=point["seq"],
                block_size=point["block_size"], heads=point["heads"],
                kvh=kvh, head_dim=16)
    cells = []

    def prof(name):
        return f"{profile_dir}/{name}" if profile_dir else None

    # --- paged_attention (decode) ---
    q, kp, vp, table, pos = _synthetic_paged(rng, **dims)
    fused = jax.jit(paged_attention)
    oracle = jax.jit(paged_attention_ref)
    out_k = fused(q, kp, vp, table, pos)
    out_r = oracle(q, kp, vp, table, pos)
    err = float(jnp.abs(out_k - out_r).max())
    cells.append(make_cell("parity_max_abs_err", "paged_attention", axes,
                           {"value": err}, prov, smoke=smoke))
    for variant, fn in (("pallas", fused), ("ref", oracle)):
        stats = time_fn(fn, q, kp, vp, table, pos, iters=iters,
                        warmup=warmup,
                        profile_dir=prof(f"paged_attention_{variant}"))
        stats["us_per_call"] = stats["mean_ms"] * 1e3
        cells.append(make_cell("kernel_us", f"paged_attention_{variant}",
                               axes, stats, prov, smoke=smoke))

    # --- chunk_attention (prefill chunk) ---
    width = min(point["seq"], max(point["block_size"], 8))
    case = _synthetic_chunk(rng, seq=point["seq"],
                            block_size=point["block_size"], width=width,
                            heads=point["heads"], kvh=kvh, head_dim=16)
    flash = jax.jit(chunk_attention)
    coracle = jax.jit(chunk_attention_ref)
    out_k = flash(*case)
    out_r = coracle(*case)
    err = float(jnp.abs(out_k - out_r).max())  # every row valid here
    cells.append(make_cell("parity_max_abs_err", "chunk_attention", axes,
                           {"value": err}, prov, smoke=smoke))
    for variant, fn in (("pallas", flash), ("ref", coracle)):
        stats = time_fn(fn, *case, iters=iters, warmup=warmup,
                        profile_dir=prof(f"chunk_attention_{variant}"))
        stats["us_per_call"] = stats["mean_ms"] * 1e3
        cells.append(make_cell("kernel_us", f"chunk_attention_{variant}",
                               axes, stats, prov, smoke=smoke))
    return cells


def _bench_model(point: dict):
    """A tiny model matched to the sweep point's head count."""
    from repro.configs import get_config
    from repro.models import build_model

    heads = point["heads"]
    kvh = max(1, heads // 2)
    cfg = get_config("paper-tiny").reduced().replace(
        n_heads=heads, n_kv_heads=kvh, head_dim=16, d_model=16 * heads)
    return build_model(jax.random.PRNGKey(0), cfg), cfg


def bench_decode_step_cells(point: dict, *, iters: int, warmup: int,
                            prov: dict, smoke: bool,
                            profile_dir: Optional[str] = None
                            ) -> list[dict]:
    """``decode_step_ms`` cells: one jitted model decode step (all slots
    live at ``seq`` tokens) for the reference gather vs the fused Pallas
    kernel — scheduler/admission overhead excluded by construction."""
    model, cfg = _bench_model(point)
    batch, seq, bs = point["batch"], point["seq"], point["block_size"]
    max_len = seq + 8
    n_table = -(-max_len // bs)
    cache0 = model.init_paged_cache(batch, max_len, cfg,
                                    n_blocks=batch * n_table + 1,
                                    block_size=bs, dtype=jnp.float32)
    table = np.asarray(
        np.random.default_rng(0).permutation(batch * n_table)
    ).reshape(batch, n_table).astype(np.int32)
    cache = cache0._replace(
        table=jnp.asarray(table),
        length=jnp.broadcast_to(jnp.int32(seq), cache0.length.shape))
    tok = jnp.zeros((batch, 1), jnp.int32)
    cells = []
    for variant in ("reference", "pallas"):
        fn = jax.jit(
            lambda t, c, k=variant: model.decode(t, c, decode_kernel=k)[0])
        stats = time_fn(fn, tok, cache, iters=iters, warmup=warmup,
                        profile_dir=(f"{profile_dir}/decode_{variant}"
                                     if profile_dir else None))
        cells.append(make_cell("decode_step_ms", variant, dict(point),
                               stats, prov, smoke=smoke))
    return cells


def bench_prefill_chunk_cells(point: dict, *, iters: int, warmup: int,
                              prov: dict, smoke: bool,
                              profile_dir: Optional[str] = None
                              ) -> list[dict]:
    """``prefill_chunk_ms`` cells: one jitted model prefill chunk
    mid-prompt (resident prefix of ``seq - W`` tokens, chunk width ``W``
    = ``block_size``), reference gather vs flash Pallas kernel."""
    model, cfg = _bench_model(point)
    batch, seq, bs = point["batch"], point["seq"], point["block_size"]
    w = min(seq // 2 or 1, bs)
    offset = seq - w
    max_len = seq + 8
    n_table = -(-max_len // bs)
    cache0 = model.init_paged_cache(batch, max_len, cfg,
                                    n_blocks=batch * n_table + 1,
                                    block_size=bs, dtype=jnp.float32)
    table = np.asarray(
        np.random.default_rng(0).permutation(batch * n_table)
    ).reshape(batch, n_table).astype(np.int32)
    cache = cache0._replace(
        table=jnp.asarray(table),
        length=jnp.broadcast_to(jnp.int32(offset), cache0.length.shape))
    toks = jnp.zeros((1, w), jnp.int32)
    qpos = offset + np.arange(w)
    dst = jnp.asarray(table[0][qpos // bs] * bs + qpos % bs)
    cells = []
    for variant in ("reference", "pallas"):
        fn = jax.jit(lambda t, c, k=variant: model.prefill_chunk(
            t, c, slot=jnp.int32(0), offset=jnp.int32(offset),
            n_valid=jnp.int32(w), dst=dst, need_logits=True,
            prefill_kernel=k)[0])
        stats = time_fn(fn, toks, cache, iters=iters, warmup=warmup,
                        profile_dir=(f"{profile_dir}/prefill_{variant}"
                                     if profile_dir else None))
        stats["chunk_width"] = w
        cells.append(make_cell("prefill_chunk_ms", variant, dict(point),
                               stats, prov, smoke=smoke))
    return cells


def bench_sharded_step_cells(point: dict, *, iters: int, warmup: int,
                             prov: dict, smoke: bool) -> list[dict]:
    """``decode_step_ms`` / ``prefill_chunk_ms`` cells under a
    ``{data, model}`` mesh: the SAME jitted reference step with params
    placed via :func:`repro.dist.sharding.model_shardings`, the paged
    pool/table via ``cache_shardings``, and activations constrained
    through an ``activation_mesh`` scope at trace time — variants
    ``sharded_dp{dp}tp{tp}`` over the serve grid.  Pallas variants are
    deliberately absent: the kernels are single-shard and the engine
    refuses them under tp>1."""
    from repro.dist.runtime import make_serve_mesh
    from repro.dist.sharding import (activation_mesh, cache_shardings,
                                     model_shardings)

    model0, cfg = _bench_model(point)
    batch, seq, bs = point["batch"], point["seq"], point["block_size"]
    w = min(seq // 2 or 1, bs)
    offset = seq - w
    max_len = seq + 8
    n_table = -(-max_len // bs)
    cache0 = model0.init_paged_cache(batch, max_len, cfg,
                                     n_blocks=batch * n_table + 1,
                                     block_size=bs, dtype=jnp.float32)
    table = np.asarray(
        np.random.default_rng(0).permutation(batch * n_table)
    ).reshape(batch, n_table).astype(np.int32)
    tok = jnp.zeros((batch, 1), jnp.int32)
    toks = jnp.zeros((1, w), jnp.int32)
    qpos = offset + np.arange(w)
    dst = jnp.asarray(table[0][qpos // bs] * bs + qpos % bs)
    cells = []
    for dp, tp in SHARDED_GRID:
        if dp * tp > len(jax.devices()):
            continue  # run_sharded_sweep re-execs with 8 forced devices
        mesh = make_serve_mesh(f"{dp},{tp}")
        variant = f"sharded_dp{dp}tp{tp}"
        if mesh is None:  # 1x1: the unsharded reference path
            model, dcache = model0, cache0
        else:
            model = jax.device_put(model0, model_shardings(model0, mesh))
            dcache = jax.device_put(cache0, cache_shardings(cache0, mesh))
        dcache = dcache._replace(
            table=jnp.asarray(table),
            length=jnp.broadcast_to(jnp.int32(seq), cache0.length.shape))

        def dec(t, c, model=model, mesh=mesh):
            with activation_mesh(mesh) if mesh is not None \
                    else contextlib.nullcontext():
                return model.decode(t, c)[0]

        stats = time_fn(jax.jit(dec), tok, dcache, iters=iters,
                        warmup=warmup)
        cells.append(make_cell("decode_step_ms", variant, dict(point),
                               stats, prov, smoke=smoke))

        pcache = dcache._replace(
            length=jnp.broadcast_to(jnp.int32(offset), cache0.length.shape))

        def pre(t, c, model=model, mesh=mesh):
            with activation_mesh(mesh) if mesh is not None \
                    else contextlib.nullcontext():
                return model.prefill_chunk(
                    t, c, slot=jnp.int32(0), offset=jnp.int32(offset),
                    n_valid=jnp.int32(w), dst=dst, need_logits=True)[0]

        stats = time_fn(jax.jit(pre), toks, pcache, iters=iters,
                        warmup=warmup)
        stats["chunk_width"] = w
        cells.append(make_cell("prefill_chunk_ms", variant, dict(point),
                               stats, prov, smoke=smoke))
    return cells


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

# the dp x tp serve grid every sharded bench walks (CPU-emulable with
# XLA_FLAGS=--xla_force_host_platform_device_count=8)
SHARDED_GRID = [(1, 1), (2, 1), (1, 2), (2, 2)]

SMOKE_SWEEP = [
    {"batch": 2, "seq": 32, "block_size": 8, "heads": 4},
    {"batch": 4, "seq": 64, "block_size": 16, "heads": 4},
]

FULL_SWEEP = [
    {"batch": b, "seq": s, "block_size": bs, "heads": h}
    for b in (2, 8)
    for s in (64, 256)
    for bs in (8, 16)
    for h in (4, 8)
]


def run_sweep(*, smoke: bool = True, iters: int = 10, warmup: int = 2,
              profile_dir: Optional[str] = None,
              sweep: Optional[list[dict]] = None) -> list[dict]:
    """Run the full microbench matrix; returns the emitted cells (one per
    metric/variant/sweep-point, plus one ``cells_emitted`` count cell the
    regression gate hard-fails on if a benchmarked path disappears)."""
    prov = provenance()
    points = sweep if sweep is not None else (SMOKE_SWEEP if smoke
                                              else FULL_SWEEP)
    cells: list[dict] = []
    for point in points:
        cells.extend(bench_kernel_cells(
            point, iters=iters, warmup=warmup, prov=prov, smoke=smoke,
            profile_dir=profile_dir))
        cells.extend(bench_decode_step_cells(
            point, iters=iters, warmup=warmup, prov=prov, smoke=smoke,
            profile_dir=profile_dir))
        cells.extend(bench_prefill_chunk_cells(
            point, iters=iters, warmup=warmup, prov=prov, smoke=smoke,
            profile_dir=profile_dir))
    paths = sorted({f"{c['metric']}/{c['variant']}" for c in cells})
    cells.append(make_cell("cells_emitted", "total", {},
                           {"value": len(cells), "paths": paths}, prov,
                           smoke=smoke))
    return cells


def run_sharded_sweep(*, smoke: bool = True, iters: int = 10,
                      warmup: int = 2) -> list[dict]:
    """The sharded microbench matrix (``--sharded``): reference decode +
    prefill-chunk steps at every dp x tp point of :data:`SHARDED_GRID`,
    plus its own ``cells_emitted/sharded`` count cell so the regression
    gate hard-fails if a mesh point silently drops out of the sweep."""
    prov = provenance()
    points = SMOKE_SWEEP[:1] if smoke else SMOKE_SWEEP
    cells: list[dict] = []
    for point in points:
        cells.extend(bench_sharded_step_cells(
            point, iters=iters, warmup=warmup, prov=prov, smoke=smoke))
    paths = sorted({f"{c['metric']}/{c['variant']}" for c in cells})
    cells.append(make_cell("cells_emitted", "sharded", {},
                           {"value": len(cells), "paths": paths}, prov,
                           smoke=smoke))
    return cells


def format_cell(cell: dict) -> str:
    s = cell["stats"]
    if "mean_ms" in s:
        body = (f"{s['mean_ms']:9.3f} ms  (p50 {s['p50_ms']:.3f}, min "
                f"{s['min_ms']:.3f}, compile {s['compile_ms']:.0f})")
    else:
        body = f"{s['value']}"
    p = cell["provenance"]
    tag = (p["compiled_backend"] or
           f"{p['backend']}+interpret")
    return f"{cell_key(cell):66s} {body}  [{tag}]"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="small sweep + few iters (the CI cell)")
    p.add_argument("--iters", type=int, default=0,
                   help="steady-state iterations (0 = 10 smoke / 30 full)")
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--history", default="",
                   help="append every cell to this JSONL perf trajectory")
    p.add_argument("--json", default="",
                   help="write this run's cells as one JSON array")
    p.add_argument("--profile-dir", default="",
                   help="activate jax.profiler around every timed region, "
                        "one trace per cell under this directory")
    p.add_argument("--sharded", action="store_true",
                   help="run the dp x tp sharded step sweep instead of the "
                        "kernel matrix (on a CPU host with fewer than 4 "
                        "devices, re-execs itself under 8 forced host "
                        "devices)")
    args = p.parse_args(argv)
    iters = args.iters or (10 if args.smoke else 30)
    if args.sharded and len(jax.devices()) < 4:
        if jax.default_backend() != "cpu":
            p.error(f"--sharded needs 4 devices; {len(jax.devices())} "
                    f"{jax.default_backend()} device(s) visible")
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env.setdefault("JAX_PLATFORMS", "cpu")
        print("# <4 devices visible; re-exec with "
              "--xla_force_host_platform_device_count=8")
        code = ("from repro.launch.microbench import main; import sys; "
                "sys.exit(main(sys.argv[1:]))")
        return subprocess.run(
            [sys.executable, "-c", code] + list(argv or sys.argv[1:]),
            env=env).returncode
    if args.sharded:
        cells = run_sharded_sweep(smoke=args.smoke, iters=iters,
                                  warmup=args.warmup)
    else:
        cells = run_sweep(smoke=args.smoke, iters=iters, warmup=args.warmup,
                          profile_dir=args.profile_dir or None)
    for cell in cells:
        print(format_cell(cell))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(cells, fh, indent=1, sort_keys=True)
        print(f"# wrote {len(cells)} cells to {args.json}")
    if args.history:
        n = append_history(args.history, cells)
        print(f"# appended {n} cells to {args.history}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
