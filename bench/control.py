"""Readings that set a cell's correctness limit: the program's widest
gap and the control's, seed by seed.

    python bench/control.py --workload qwen2.5-3b.chat --seeds 21,22,23 \
        --seconds 15

For each seed, in one process: the cell is built from that seed and
serves its own traffic for ``--seconds``, as in a run; then, over the
same sample of served requests that a run checks, the plain float32
reference reads two gaps per position.  ``program`` is how far the
served token's reference logit lies below the reference's best; the
control is the reference itself computed with float8 (e4m3) operands,
one precision step below the configuration's bfloat16, and ``control``
is how far the token it puts first lies below the reference's best.
Both go through the run's own comparison (``run.check``/``run.passed``),
so a sound program reads ``correct: true`` and the control ``false``.
Prints one JSON line per seed.  A tool for setting limits; the
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

import run as bench_run


def judge(run, prog, ctrl, failed: int) -> dict:
    """The program's gaps ``prog`` and the control's ``ctrl``, each put
    through the run's own comparison: what each compared, whether it
    passed, and the limits."""
    out = {}
    for side, flat in (("program", prog), ("control", ctrl)):
        checks = bench_run.check(run, flat, failed)
        out[side] = {"correct": bench_run.passed(checks),
                     **{k: c["value"] for k, c in checks.items()}}
    out["limits"] = {k: c["limit"] for k, c in checks.items()}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        _, run, device = bench_run.start(args.workload, seeds[0],
                                         args.seconds, False)
    except bench_run.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for seed in seeds:
        r = bench_run.Run(bench_dir=run.bench_dir, cell=run.cell,
                          conf=run.conf, mix=run.mix, seed=seed,
                          seconds=args.seconds, trace=False, ref=run.ref)
        weights, model, engine = bench_run.build(r)
        bench_run.measure(r, engine)
        del model, engine
        gc.collect()
        picked = bench_run.picked(r)
        failed = bench_run.loadgen.failed(r.records, r.end)
        prog = bench_run.gaps(r, weights, picked)
        ctrl = bench_run.gaps(r, weights, picked, quant="fp8")
        print(json.dumps({"seed": seed, "requests": len(picked),
                          **judge(r, prog, ctrl, failed)}), flush=True)
        del weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
