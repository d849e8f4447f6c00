"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch paper-tiny --steps 200 \
        --batch 16 --seq 64 --ckpt-dir /tmp/run1 [--fact-rank 0.25 --solver random]

Production behaviours exercised here (and relied on at scale):
  * always-resume: restores the newest complete checkpoint before training —
    any crash/preemption is survivable by simply relaunching the same command;
  * SIGTERM/SIGINT → checkpoint-then-exit (clean preemption handling);
  * step-indexed data: batch k is a pure function of (seed, k), so resume and
    elastic re-sharding reproduce the exact stream;
  * Greenformer factorization-by-design via --fact-rank (the paper's use
    case 1) — one flag factorizes the model before training;
  * optional low-rank gradient compression (--grad-comp-rank) on the DP axis.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import auto_fact
from repro.core.gradcomp import init_compressor
from repro.data import markov_lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import AdamW, linear_warmup_cosine
from repro.train import TrainState, make_train_step


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-tiny")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--fact-rank", type=float, default=0.0,
                   help="Greenformer factorization-by-design rank ratio")
    p.add_argument("--solver", default="random")
    p.add_argument("--grad-comp-rank", type=int, default=0)
    p.add_argument("--reduced", action="store_true",
                   help="use the reduced (smoke) config of the arch")
    args = p.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced or args.arch != "paper-tiny":
        cfg = cfg.reduced() if args.arch != "paper-tiny" else cfg
    if args.reduced and args.arch == "paper-tiny":
        cfg = cfg.reduced()

    key = jax.random.PRNGKey(args.seed)
    model = build_model(key, cfg)
    if args.fact_rank:
        model, report = auto_fact(
            model, args.fact_rank, solver=args.solver, key=key,
            return_report=True)
        print(report.summary())

    opt = AdamW(linear_warmup_cosine(args.lr, args.warmup, args.steps),
                weight_decay=0.01, master_fp32=False)
    compressor = None
    compression_axis = None
    if args.grad_comp_rank:
        zero_grads = jax.tree_util.tree_map(
            lambda p: None if p is None else jnp.zeros_like(p), model)
        compressor = init_compressor(zero_grads, args.grad_comp_rank, key)
    state = TrainState(model=model, opt=opt.init(model),
                       step=jnp.zeros((), jnp.int32), compressor=compressor)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt is not None:
        found, state = ckpt.restore_latest(state)
        if found is not None:
            start = found
            print(f"[resume] restored step {found}")

    step_fn = jax.jit(make_train_step(
        opt, accum=args.accum, compression_axis=compression_axis))

    stop = {"now": False}

    def _handler(sig, frame):
        stop["now"] = True

    signal.signal(signal.SIGTERM, _handler)
    signal.signal(signal.SIGINT, _handler)

    t0 = time.time()
    metrics = {}
    for i in range(start, args.steps):
        b = markov_lm_batch(i, batch=args.batch, seq=args.seq,
                            vocab=cfg.vocab, seed=args.seed)
        state, metrics = step_fn(state, {"tokens": b.tokens,
                                         "labels": b.labels})
        if i % 20 == 0 or i == args.steps - 1:
            m = {k: round(float(v), 4) for k, v in metrics.items()}
            print(f"step {i:5d} {m} ({(time.time()-t0):.1f}s)", flush=True)
        if ckpt is not None and (
                (i + 1) % args.ckpt_every == 0 or stop["now"]
                or i == args.steps - 1):
            ckpt.save(i + 1, state)
        if stop["now"]:
            print(f"[preempt] checkpointed at step {i + 1}, exiting")
            return 0
    if metrics:
        print(f"done: final loss {float(metrics['loss']):.4f}")
    else:
        print(f"done: nothing to do (resumed at step {start} >= "
              f"{args.steps})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
