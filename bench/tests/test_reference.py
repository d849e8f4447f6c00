"""The plain float32 reference against the program's own model, at a
reduced size on the CPU: prefill followed by cached decode must give the
reference's logits, dense and factorized.  Also that the benchmark hands
the program the model ``auto_fact`` would make, and that the fp8 control
is a different computation."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import reference
from conftest import BENCH

TINY = json.loads((BENCH / "testdata" / "tiny.json").read_text())


def tiny(factorize: bool, dtype: str = "float32") -> dict:
    conf = json.loads(json.dumps(TINY))
    conf["torch_dtype"] = dtype
    conf["program"]["replace"]["dtype"] = dtype
    if factorize:
        conf["factorize"] = {"rank": 0.5, "exclude": ["embed", "lm_head"]}
    return conf


@pytest.mark.parametrize("factorize", [False, True], ids=["dense", "led"])
def test_prefill_then_cached_decode_matches_reference(factorize):
    conf = tiny(factorize)
    weights = reference.make_weights(conf, 2**32 + 11)
    cfg = program.arch_config(conf)
    model = program.load_model(conf, cfg, weights)
    tokens = np.random.default_rng(0).integers(0, conf["vocab_size"], 24)
    n_prompt = 13
    want = np.asarray(reference.logits(conf, weights, tokens))

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


def test_scores_agree_with_logits():
    conf = tiny(False)
    weights = reference.make_weights(conf, 3)
    tokens = np.random.default_rng(1).integers(0, conf["vocab_size"], 16)
    full = np.asarray(reference.logits(conf, weights, tokens))
    best, target, top = reference.scores(conf, weights, tokens, block=8,
                                         pad_to=8)
    np.testing.assert_allclose(best, full[:-1].max(-1), rtol=1e-5)
    np.testing.assert_array_equal(top, full[:-1].argmax(-1))
    np.testing.assert_allclose(
        target, full[np.arange(15), tokens[1:]], rtol=1e-5)
    # the padding to a longer length leaves every real position alone
    best2, _, _ = reference.scores(conf, weights, tokens, block=8, pad_to=64)
    np.testing.assert_allclose(best2, best, rtol=1e-5)


def test_control_is_a_lower_precision():
    conf = tiny(False, "bfloat16")
    weights = reference.make_weights(conf, 4)
    tokens = np.random.default_rng(2).integers(0, conf["vocab_size"], 64)
    exact = reference.served_gaps(conf, weights, tokens[:32], tokens[32:],
                                  block=8, pad_to=64)
    ctrl = reference.served_gaps(conf, weights, tokens[:32], tokens[32:],
                                 quant="fp8", block=8, pad_to=64)
    assert exact.shape == ctrl.shape == (32,)
    assert (ctrl >= 0).all() and ctrl.max() > 0


def test_led_model_is_what_auto_fact_makes():
    program.import_program()
    from repro.core import auto_fact
    from repro.models import build_model

    conf = tiny(True)
    cfg = program.arch_config(conf)
    ours = program.load_model(conf, cfg, reference.make_weights(conf, 5))
    theirs = auto_fact(build_model(jax.random.PRNGKey(0), cfg), 0.5,
                       solver="random", exclude=["embed", "lm_head"],
                       gate=False)
    assert (jax.tree_util.tree_structure(ours)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seeds_above_32_bits_differ():
    a = reference.seed_key(5)
    b = reference.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
