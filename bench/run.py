"""One run of one benchmark cell, on the chip it is started on.

    python bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 30 --trace 0

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell names a configuration (``bench/configs/<name>.json``,
with its plain reference ``bench/<reference>.py``) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/generators/<generator>.py``);
each metric is read by ``bench/metrics/<family>.py``, where the family is
the metric's name up to its first dot.

One process loads, warms up, measures and checks, since a chip belongs to
one process: the weights are made on the device from the seed, the
program's model and ``ContinuousEngine`` are built from them, every
program the traffic uses is compiled (or loaded from the compile cache in
the checkout) and run once, then the HTTP front door serves the mix for
``--seconds`` while a client on this process's asyncio loop times every
request from when it was due.  With ``--trace 1`` the same run is traced
in the middle of its window and the per-layer metrics are printed instead
of the end-to-end ones.

When the window has closed, the run waits for the server to drain, reads
the device's peak memory, frees the program, and runs the plain float32
reference over a sample of the served requests drawn from the seed: no
served token's reference logit may lie further below the reference's best
than the configuration's limit.  The numbers compared go to standard
error and, under ``checks``, last in the result line, which is the last
line of standard output.

Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402
import program  # noqa: E402
import trace_reduce as tr  # noqa: E402

PLATFORM = "tpu"
MAX_PENDING = 4096          # the server never answers 429 in a run
DRAIN_S = 120.0             # wait for the server to finish after the close
TRACE_S = 6.0               # length of the traced slice of the window
CHUNK = 256                 # the engine's prefill chunk (program.make_engine)


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclass
class Run:
    """What the metric readers read."""

    bench_dir: Path
    cell: dict
    conf: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t0: float = 0.0
    end: float = 0.0
    setup_s: float = 0.0
    records: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    work: dict = field(default_factory=dict)      # traced slice, see Tracer
    device_trace: dict = field(default_factory=dict)
    peak: dict = field(default_factory=dict)
    ref: object = None                            # the plain reference


# -- finding things by name ---------------------------------------------------


def load_module(path: Path):
    """The module in the file ``path``, imported once per process."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def reader(bench_dir: Path, name: str):
    """The module that reads metric ``name``: ``metrics/<family>.py``."""
    return load_module(bench_dir / "metrics" / f"{name.split('.')[0]}.py")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    name = cell["name"]
    if not trace:
        return [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    e2e = {m["name"] for m in cell_metrics(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in e2e
                             else [])]


def resolve(bench_dir: Path, workload: str):
    """``(benchmark, cell, configuration, mix)`` of a cell name."""
    bench = tr.load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = tr.load_json(bench_dir.parent / entry["file"])
    mix = tr.load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, conf, mix


# -- the device ---------------------------------------------------------------


def check_device(chips: int, require: bool) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require and d.platform != PLATFORM:
        raise NoChip(f"no {PLATFORM} device: JAX found {d.platform!r} "
                     f"({d.device_kind}); the benchmark never falls back")
    if len(devs) < chips:
        raise NoChip(f"{chips} devices needed, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:chips]]
    return int(max(peaks))


# -- set-up -------------------------------------------------------------------


def warm_up(engine, vocab: int) -> None:
    """Compile and run once every program the traffic uses: the final
    chunk at each bucket width, a middle chunk, the bind and the decode
    step.  Prompts are random ids that no run sends again."""
    rng = np.random.default_rng(0)
    for n in sorted({CHUNK + 1, *engine.buckets}):
        engine.submit(rng.integers(0, vocab, n).astype(np.int32),
                      max_new_tokens=2)
    engine.run()
    engine.reset_stats()


def build(run: Run, fault=None):
    """The weights (from the seed), the program's model and its engine,
    warmed up."""
    conf = run.conf
    weights = run.ref.make_weights(conf, run.seed)
    cfg = program.arch_config(conf)
    model = program.load_model(conf, cfg, weights)
    engine = program.make_engine(model, cfg, run.mix["engine"], run.seed)
    if fault is not None:
        fault(engine)
    warm_up(engine, conf["vocab_size"])
    return weights, model, engine


# -- tracing ------------------------------------------------------------------


class Tracer:
    """Traces a slice of the window and records, for the steps that ran
    wholly inside it, the work each step program did: the context of
    every live decode slot, and the offset, length and finality of every
    prefill chunk.  Host spans (``bench.*``) wrap the engine's step and
    its phases."""

    def __init__(self, engine, out_dir: Path):
        self.engine, self.out_dir = engine, out_dir
        self.on = False
        self.steps: list = []       # committed: (decode contexts, chunks)
        self._pending = None
        self._thread = None
        self._wrap()

    def _wrap(self):
        import jax

        eng = self.engine
        step, decode, run_chunk = eng.step, eng._decode, eng._run_chunk
        advance, flush = eng._advance_prefills, eng._flush_table
        tracer = self

        def traced_step():
            on = tracer.on
            tracer._pending = ([], [])
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                out = step()
            if on and tracer.on:
                tracer.steps.append(tracer._pending)
            return out

        def traced_decode(*a):
            slots = eng.scheduler.slots
            ctx = [len(s.request.prompt) + len(s.tokens)
                   for s in slots if s is not None]
            tracer._pending[0].extend(ctx)
            with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
                return decode(*a)

        def traced_chunk(task, n, w):
            final = task.consumed + n >= task.plen
            tracer._pending[1].append((task.consumed, n, final))
            return run_chunk(task, n, w)

        def traced_advance():
            with jax.profiler.TraceAnnotation("bench.prefill_chunks"):
                return advance()

        def traced_flush():
            with jax.profiler.TraceAnnotation("bench.table_flush"):
                return flush()

        eng.step, eng._decode, eng._run_chunk = (traced_step, traced_decode,
                                                 traced_chunk)
        eng._advance_prefills, eng._flush_table = traced_advance, traced_flush

    def start_at(self, t_start: float, length: float):
        def body():
            import jax
            import shutil

            delay = t_start - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window"):
                self.on = True
                time.sleep(length)
                self.on = False
            jax.profiler.stop_trace()

        self._thread = threading.Thread(target=body, name="bench-trace")
        self._thread.start()

    def join(self):
        self._thread.join()


# -- the window ---------------------------------------------------------------


def serve_window(run: Run, engine, plan, tracer=None, sampler=None) -> None:
    """Serve the plan over HTTP for the window; fill ``run``'s records and
    counters; return once the server has drained.  ``sampler(engine)`` is
    called every quarter second of the window, from a thread of its own."""
    program.import_program()
    from repro.serve.http import BackgroundServer

    with BackgroundServer(engine, max_pending=MAX_PENDING) as bg:
        run.t0 = time.monotonic() + 0.05
        run.end = run.t0 + run.seconds
        run.setup_s = run.t0 - T_START
        if tracer is not None:
            tracer.start_at(run.t0 + max(0.0, (run.seconds - TRACE_S) / 2),
                            min(TRACE_S, run.seconds))
        stop = threading.Event()
        if sampler is not None:
            def sample_loop():
                while not stop.wait(0.25):
                    sampler(engine)

            sampling = threading.Thread(target=sample_loop,
                                        name="bench-sampler")
            sampling.start()
        try:
            run.records = asyncio.run(loadgen.drive(
                bg.host, bg.port, plan, run.t0, run.seconds))
        finally:
            stop.set()
            if sampler is not None:
                sampling.join()
        run.counters = dict(engine.prefill_stats())
        deadline = time.monotonic() + DRAIN_S
        while not (engine.scheduler.idle and not bg.server._routes):
            if time.monotonic() > deadline:
                raise RuntimeError("the server did not drain after the "
                                   "window closed")
            time.sleep(0.05)
    if tracer is not None:
        tracer.join()
        run.work = {"steps": tracer.steps}


# -- correctness --------------------------------------------------------------


def sample(records: list, seed: int, tokens: int, most: int) -> list:
    """Requests to check, drawn from the seed: the longest, then others in
    a seeded order until ``tokens`` served tokens or ``most`` requests.
    A request cut at the close is checked on the tokens it served by
    then: each streamed token is final."""
    done = [r for r in records if r.tokens and r.error is None]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.tokens), r.session,
                             r.turn))
    longest = done.pop()
    rng = np.random.default_rng([seed, 4])
    out, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(done)):
        if n >= tokens or len(out) >= most:
            break
        out.append(done[i])
        n += len(done[i].tokens)
    return out


def gaps(run: Run, weights: dict, picked: list, quant: str = "none"):
    """Reference gaps of the served tokens of ``picked`` (with ``quant``,
    the control's), one array for all."""
    out = [run.ref.served_gaps(run.conf, weights, r.prompt, r.tokens,
                           quant=quant) for r in picked]
    return np.concatenate(out) if out else np.zeros(0)


def check(run: Run, flat, failed: int) -> dict:
    """The numbers compared, each with its limit."""
    return {
        "max_gap": {"value": float(flat.max()) if flat.size else
                    float("inf"), "limit": run.conf["check"]["max_gap"]},
        "failed": {"value": failed, "limit": 0},
        "checked_tokens": {"value": int(flat.size),
                           "limit": run.mix["check"]["min_tokens"]},
    }


def passed(checks: dict) -> bool:
    return (checks["max_gap"]["value"] <= checks["max_gap"]["limit"]
            and checks["failed"]["value"] <= checks["failed"]["limit"]
            and checks["checked_tokens"]["value"]
            >= checks["checked_tokens"]["limit"])


# -- the run ------------------------------------------------------------------


def breakdown(run: Run) -> dict:
    lo, hi = tr.window(run.device_trace)
    plane = tr.device_planes(run.device_trace)[0]
    return {"device_ops": tr.top_ops(plane, lo, hi),
            "idle_gaps": tr.idle_gaps(run.device_trace, plane, lo, hi)}


def start(workload: str, seed: int, seconds: float, trace: bool, *,
          bench_dir: Path = BENCH, require_tpu: bool = True):
    """Find the cell, check the device and turn on the compile cache;
    returns ``(benchmark, run, device)``."""
    bench, cell, conf, mix = resolve(bench_dir, workload)
    program.import_program()
    import jax

    device = check_device(cell["chips"], require_tpu)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(bench_dir=bench_dir, cell=cell, conf=conf, mix=mix,
              seed=seed, seconds=float(seconds), trace=bool(trace))
    run.ref = load_module(bench_dir / f"{conf['reference']}.py")
    return bench, run, device


def measure(run: Run, engine, sampler=None) -> None:
    """Serve the run's traffic over the window (traced if asked)."""
    gen = load_module(run.bench_dir / "generators"
                      / f"{run.mix['generator']}.py")
    plan = gen.generate(run.mix, run.seed, run.seconds,
                        run.conf["vocab_size"])
    trace_dir = run.bench_dir.parent / ".bench_trace" / run.cell["name"]
    tracer = Tracer(engine, trace_dir) if run.trace else None
    serve_window(run, engine, plan, tracer, sampler)
    if run.trace:
        run.device_trace = tr.load_xplane(tr.find_xplane(trace_dir))


def execute(args, *, bench_dir: Path = BENCH, require_tpu: bool = True,
            fault=None) -> dict:
    """One run; returns the result line's object."""
    bench, run, device = start(args.workload, args.seed, args.seconds,
                               args.trace, bench_dir=bench_dir,
                               require_tpu=require_tpu)
    weights, model, engine = build(run, fault)
    measure(run, engine)
    device["memory_peak_bytes"] = memory_peak(run.cell["chips"])
    del model, engine
    gc.collect()

    out = {"correct": False, "attempted": int(sum(
        r.due < run.end for r in run.records)),
        "failed": loadgen.failed(run.records, run.end)}
    if run.trace:
        run.peak = tr.peaks(device["kind"])
        device["busy_s"], device["window_s"] = tr.busy_s(run.device_trace)
    metrics = {}
    for m in cell_metrics(bench, run.cell, run.trace):
        value = reader(bench_dir, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if run.trace:
        out["breakdown"] = breakdown(run)
        run.device_trace = {}

    t = time.monotonic()
    checks = check(run, gaps(run, weights, picked(run)), out["failed"])
    print(f"reference check: {time.monotonic() - t:.1f} s", file=sys.stderr)
    out["correct"] = passed(checks)
    out["checks"] = checks
    return out


def picked(run: Run) -> list:
    chk = run.mix["check"]
    return sample(run.records, run.seed, chk["sample_tokens"],
                  chk["sample_requests"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        out = execute(args)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
