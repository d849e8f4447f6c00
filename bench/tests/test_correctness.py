"""What decides ``correct``, at a size the CPU runs: the served tokens of
the program pass the comparison with the reference, the fp8 control put
in the program's place fails it, and a run whose served tokens are
altered where the engine produces them comes out not correct."""

import json

import numpy as np

import control
import run as bench_run
from conftest import Args

# the tiny configuration's limit on the widest gap, in logits, set from
# CPU readings like the cells' own: bf16 serving read 0.002 and 0.0 on
# seeds 5 and 6, the fp8 control 0.017 and 0.077
TINY_LIMIT = 0.01


def served(run, engine, n=6):
    """Greedy completions of ``n`` prompts of the tiny chat mix's shape,
    served by the engine itself."""
    rng = np.random.default_rng(run.seed)
    prompts = [rng.integers(0, run.conf["vocab_size"], int(k)).tolist()
               for k in rng.integers(16, 60, n)]
    for p in prompts:
        engine.submit(np.asarray(p, np.int32), max_new_tokens=12)
    done = sorted(engine.run(), key=lambda c: c.uid)
    return [(p, c.tokens) for p, c in zip(prompts, done)]


def test_control_fails_where_the_program_passes(checkout):
    _, run, _ = bench_run.start("tiny.tiny_chat", 7, 1.0, False,
                                bench_dir=checkout, require_tpu=False)
    assert run.conf["check"]["max_gap"] == TINY_LIMIT
    weights, model, engine = bench_run.build(run)
    pairs = served(run, engine)
    prog = np.concatenate([run.ref.served_gaps(run.conf, weights, p, t)
                           for p, t in pairs])
    ctrl = np.concatenate([run.ref.served_gaps(run.conf, weights, p, t,
                                               quant="fp8")
                           for p, t in pairs])
    assert prog.size == ctrl.size == 72
    assert prog.max() <= TINY_LIMIT < ctrl.max()
    # the control tool judges both through the run's own comparison
    got = control.judge(run, prog, ctrl, failed=0)
    assert got["program"]["correct"] and not got["control"]["correct"]
    assert got["control"]["max_gap"] == float(ctrl.max())
    assert got["limits"]["max_gap"] == TINY_LIMIT


def test_a_token_altered_where_produced_is_caught(checkout):
    vocab = json.loads((checkout / "configs" / "tiny.json").read_text())[
        "vocab_size"]

    def alter_decoded_tokens(engine):
        decode = engine._decode

        def faulty(*a):
            cache, state, nxt, done = decode(*a)
            return cache, state, (nxt + 1) % vocab, done

        engine._decode = faulty

    out = bench_run.execute(Args(seed=9, seconds=4.0), bench_dir=checkout,
                            require_tpu=False, fault=alter_decoded_tokens)
    assert out["checks"]["checked_tokens"]["value"] > 0
    assert out["checks"]["max_gap"]["value"] > TINY_LIMIT
    assert not out["correct"]


def test_sample_takes_the_longest_and_served_tokens_only():
    from loadgen import Record

    def rec(i, plen, ntok, **kw):
        r = Record(session=i, turn=0, due=0.0, prompt=[1] * plen, max_new=9)
        r.tokens = list(range(ntok))
        for k, v in kw.items():
            setattr(r, k, v)
        return r

    recs = [rec(0, 10, 5, finish="length"), rec(1, 50, 3),  # cut at close
            rec(2, 99, 9, error="stream closed early"), rec(3, 80, 0),
            rec(4, 20, 4, finish="length")]
    got = bench_run.sample(recs, seed=1, tokens=100, most=10)
    assert got[0].session == 1              # the longest that served
    assert {r.session for r in got} == {0, 1, 4}
    assert bench_run.sample(recs, seed=1, tokens=3, most=10) == [recs[1]]
