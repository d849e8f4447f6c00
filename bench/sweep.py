"""Find a cell's knee: the highest offered rate the engine sustains.

    python bench/sweep.py --workload qwen2.5-3b.chat --seed 11 \
        --rates 0.3,0.6,0.9 --seconds 51

One process builds the cell once, then serves its mix at each session
rate in turn for a window of ``--seconds``, draining between rates.  It samples the
engine's pending queue every quarter second and prints, per rate, one
JSON line: the queue's growth over the second half of the window, the
client-side tails and the tokens per second.  The knee is the highest
rate whose pending queue does not grow through the window.  A tool for
defining a cell, never part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

import run as bench_run


def queue_growth(samples, t0: float, end: float) -> float:
    """Pending requests gained per second over the window's second half
    (least squares)."""
    pts = [(t, n) for t, n in samples if (t0 + end) / 2 <= t <= end]
    if len(pts) < 2:
        return 0.0
    t, n = np.asarray(pts, float).T
    return float(np.polyfit(t - t[0], n, 1)[0])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated session rates")
    args = p.parse_args(argv)
    try:
        bench, run, device = bench_run.start(args.workload, args.seed,
                                             args.seconds, False)
    except bench_run.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    weights, model, engine = bench_run.build(run)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        # a seed of its own per rate: other system prompts, so the prefix
        # cache starts as cold at each rate as in a run
        r = bench_run.Run(bench_dir=run.bench_dir, cell=run.cell,
                          conf=run.conf, mix=dict(run.mix, session_rate=rate),
                          seed=args.seed + i, seconds=args.seconds,
                          trace=False, ref=run.ref)
        samples = []
        engine.reset_stats()
        bench_run.measure(r, engine, sampler=lambda e: samples.append(
            (time.monotonic(), e.scheduler.n_pending)))
        line = {"rate": rate, "queue_growth_per_s": queue_growth(
            samples, r.t0, r.end),
            "pending_end": samples[-1][1] if samples else None,
            "attempted": int(sum(x.due < r.end for x in r.records)),
            "later_turns": int(sum(x.due < r.end and x.turn > 0
                                   for x in r.records)),
            "failed": bench_run.loadgen.failed(r.records, r.end),
            "tok_s": bench_run.loadgen.tokens_in(r.records, r.t0, r.end)
            / r.seconds}
        for name in ("ttft_p95_ms", "itl_p95_ms", "prefix_hit_share"):
            line[name] = bench_run.reader(run.bench_dir, name).read(r)
        print(json.dumps(line), flush=True)
        gc.collect()
    device["memory_peak_bytes"] = bench_run.memory_peak(run.cell["chips"])
    print(json.dumps({"device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
