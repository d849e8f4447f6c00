"""Fused Pallas paged-attention decode kernel: parity + serving identity.

Three layers of guarantee, all running in interpret mode on CPU (chosen
by platform; ``tests/test_chip_compile.py`` compiles the same kernel for a
described TPU):

* kernel vs oracle — :func:`repro.kernels.paged_attention` must match the
  pure-jnp :func:`repro.kernels.ref.paged_attention_ref` AND the
  dense-gather attention it replaces (materialized pool gather + masked
  softmax, the exact math of ``Attention._decode_paged``'s reference
  branch) to fp32 tolerance.  Property-based via the ``tests/_hyp`` shim:
  random block tables, ragged per-slot positions, GQA/MQA head ratios,
  sentinel blocks past each slot's reservation.
* in-kernel masking — sentinel blocks and ``kpos > pos`` lanes contribute
  exactly zero; a fully-masked slot (all-sentinel table, the state of a
  released decode slot) emits zeros, not NaN.
* serving identity — greedy decode through ``ContinuousEngine`` with
  ``decode_kernel="pallas"`` is bit-identical to the dense-gather
  reference path on seeded shared-prefix traces, including the
  cache-full frozen-slot eviction path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.kernels import paged_attention, paged_attention_ref
from repro.kernels.paged_attention import pages_per_block, walk_bound
from repro.models import build_model
from repro.serve import ContinuousEngine, make_trace, replay

NEG_INF = -1e30


# ---- case construction -------------------------------------------------------


def _make_case(seed, *, batch, heads, kvh, hd, bs, n_table, extra_blocks=2,
               dtype=jnp.float32):
    """A well-formed paged layout: each slot owns ``pos // bs + 1`` distinct
    pool blocks (the manager's reservation invariant), the rest of its
    table row is the sentinel.  Positions are ragged across slots."""
    rng = np.random.default_rng(seed)
    n_blocks = batch * n_table + extra_blocks
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (batch, heads, hd), dtype)
    k_pool = jax.random.normal(kk, (kvh, n_blocks, bs, hd), dtype)
    v_pool = jax.random.normal(kv, (kvh, n_blocks, bs, hd), dtype)
    pos = rng.integers(0, n_table * bs, batch).astype(np.int32)
    table = np.full((batch, n_table), n_blocks, np.int32)
    perm = rng.permutation(n_blocks)
    off = 0
    for b in range(batch):
        need = pos[b] // bs + 1
        table[b, :need] = perm[off:off + need]
        off += need
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos)


def _dense_gather_attend(q, k_pool, v_pool, table, pos):
    """The attention the kernel replaces: materialize the dense per-slot
    gather from the pool (sentinel rows clip, like jnp out-of-bounds
    gathers), then masked-softmax single-query attention in fp32 — the
    same math as ``Attention._decode_paged``'s reference branch."""
    q, k_pool, v_pool = (np.asarray(a, np.float32)
                         for a in (q, k_pool, v_pool))
    table, pos = np.asarray(table), np.asarray(pos)
    batch, heads, hd = q.shape
    kvh, nb, bs, _ = k_pool.shape
    group = heads // kvh
    kpos = np.arange(table.shape[1] * bs)
    rows = np.minimum(table[:, kpos // bs] * bs + kpos[None, :] % bs,
                      nb * bs - 1)
    # head-major pool -> flat (nb * bs, kvh, hd) rows
    gk = k_pool.transpose(1, 2, 0, 3).reshape(nb * bs, kvh, hd)[rows]
    gv = v_pool.transpose(1, 2, 0, 3).reshape(nb * bs, kvh, hd)[rows]
    valid = kpos[None, :] <= pos[:, None]
    qg = q.reshape(batch, kvh, group, hd)
    logits = np.einsum("bkgd,bskd->bkgs", qg, gk) / np.sqrt(hd)
    logits = np.where(valid[:, None, None, :], logits, NEG_INF)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.einsum("bkgs,bskd->bkgd", probs, gv)
    return out.reshape(batch, heads, hd)


def _assert_three_way(q, k_pool, v_pool, table, pos, tol=1e-5):
    y = paged_attention(q, k_pool, v_pool, table, pos)
    yr = paged_attention_ref(q, k_pool, v_pool, table, pos)
    yd = _dense_gather_attend(q, k_pool, v_pool, table, pos)
    assert y.shape == yr.shape == yd.shape
    assert y.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=tol, rtol=tol, err_msg="kernel vs ref")
    np.testing.assert_allclose(np.asarray(y, np.float32), yd,
                               atol=tol, rtol=tol,
                               err_msg="kernel vs dense gather")


# ---- kernel vs oracle vs dense gather ----------------------------------------


@pytest.mark.parametrize("heads,kvh", [(4, 4), (4, 2), (4, 1), (1, 1)])
def test_kernel_parity_head_ratios(heads, kvh):
    """MHA, GQA, and MQA all hit the same kernel; every ratio must match
    both oracles."""
    q, kp, vp, table, pos = _make_case(7, batch=3, heads=heads, kvh=kvh,
                                       hd=16, bs=4, n_table=5)
    _assert_three_way(q, kp, vp, table, pos)


def test_kernel_parity_block_size_one_and_single_slot():
    q, kp, vp, table, pos = _make_case(11, batch=1, heads=2, kvh=2, hd=8,
                                       bs=1, n_table=6)
    _assert_three_way(q, kp, vp, table, pos)


def test_kernel_parity_bf16_pool():
    """bf16 pools (the serving cache dtype at scale) accumulate in fp32."""
    q, kp, vp, table, pos = _make_case(3, batch=2, heads=4, kvh=2, hd=16,
                                       bs=4, n_table=4, dtype=jnp.bfloat16)
    _assert_three_way(q, kp, vp, table, pos, tol=2e-2)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=15, deadline=None)
def test_kernel_parity_random_layouts(seed):
    """Property: random block tables, ragged positions, GQA ratios, block
    sizes, and sentinel tails — fused == reference == dense-gather to
    fp32 tolerance."""
    rng = np.random.default_rng(seed)
    heads, kvh = [(1, 1), (2, 1), (4, 2), (4, 4), (6, 3)][
        int(rng.integers(0, 5))]
    q, kp, vp, table, pos = _make_case(
        int(rng.integers(0, 2**31)),
        batch=int(rng.integers(1, 5)), heads=heads, kvh=kvh,
        hd=int(rng.choice([4, 8, 16])), bs=int(rng.integers(1, 9)),
        n_table=int(rng.integers(1, 7)),
        extra_blocks=int(rng.integers(0, 4)))
    _assert_three_way(q, kp, vp, table, pos)


# ---- the walk bound ----------------------------------------------------------

# bs 64 gives 8-page (512-key) compute blocks, so a 20-entry table is three
# compute blocks (8, 8, 4): positions can sit mid-block or on a block edge
WALK = dict(heads=4, kvh=2, hd=8, bs=64, n_table=20)


def _walk_case(name):
    """``(q, k_pool, v_pool, table, pos)`` of one walk-bound edge case."""
    if name == "block_size_one":
        return _make_case(5, batch=3, heads=4, kvh=2, hd=8, bs=1, n_table=9)
    q, kp, vp, table, pos = _make_case(29, batch=4, **WALK)
    nb, bs, nt = kp.shape[1], WALK["bs"], WALK["n_table"]
    keys, park = pages_per_block(bs, nt) * bs, nt * bs
    table, pos = np.array(table), np.array(pos)
    # every slot owning its whole row, so that the position alone bounds it
    full = np.random.default_rng(3).permutation(nb)[:table.size].reshape(
        table.shape).astype(np.int32)
    if name == "pos_mid_block":
        table, pos[:] = full, [keys + 77, 5, 2 * keys + 40, keys // 2]
    elif name == "pos_on_block_edge":
        table, pos[:] = full, [keys - 1, keys, 2 * keys - 1, 2 * keys]
    elif name == "ragged_table_width":   # every slot into the short block
        table, pos[:] = full, [park - 1, 2 * keys + 1, park - 2,
                               2 * keys + 3 * bs]
    elif name == "released_slot":        # all sentinel, parked
        table[1], pos[1] = nb, park
    elif name == "parked_prefill":       # a 7-entry reservation, parked
        table, pos[2] = full, park
        table[2, 7:] = nb
    elif name == "hole_in_bound":
        table, pos[0] = full, 2 * keys + 5
        table[0, keys // bs + 1] = nb
    elif name == "all_idle":
        table[:], pos[:] = nb, park
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos)


@pytest.mark.parametrize("name", [
    "pos_mid_block", "pos_on_block_edge", "ragged_table_width",
    "block_size_one", "released_slot", "parked_prefill", "hole_in_bound",
    "all_idle"])
def test_kernel_parity_walk_bound(name):
    """The bounded walk equals the oracle wherever the bound falls: inside
    or on the edge of a compute block, in a last block the table width
    leaves short, at one-token blocks; a released slot and an all-idle
    batch emit zeros, not NaN; a parked prefilling slot and a sentinel
    hole inside the bound are masked as the oracle masks them."""
    q, kp, vp, table, pos = _walk_case(name)
    y = np.asarray(paged_attention(q, kp, vp, table, pos))
    yr = np.asarray(paged_attention_ref(q, kp, vp, table, pos))
    assert np.isfinite(y).all()
    np.testing.assert_allclose(y, yr, atol=1e-5, rtol=1e-5)
    walk = np.asarray(walk_bound(np.asarray(table), np.asarray(pos),
                                 kp.shape[2], kp.shape[1]))
    assert (y[walk == 0] == 0.0).all()
    if name in ("released_slot", "all_idle"):
        assert (walk == 0).sum() == (4 if name == "all_idle" else 1)
    # the dense gather attends whatever a sentinel clips to: holes aside,
    # every slot with a position in range matches it too
    live = np.asarray(pos) < table.shape[1] * kp.shape[2]
    if name == "hole_in_bound":
        live[0] = False
    np.testing.assert_allclose(
        y[live], _dense_gather_attend(q, kp, vp, table, pos)[live],
        atol=1e-5, rtol=1e-5, err_msg="kernel vs dense gather")


def test_walk_bound_rows():
    """One row per kind: a live slot stops at its position's block; a hole
    stays inside the bound (it is the last mapped entry that counts); a
    parked prefilling slot walks its reservation; an idle row walks
    nothing; a full slot walks the whole table.  Host (numpy) and device
    (jax) give the same bound."""
    nb, bs, nt = 50, 4, 6
    s = nb  # the sentinel
    table = np.array([[3, 4, 5, s, s, s],     # live at pos 9 -> 3 entries
                      [6, s, 7, 8, s, s],     # hole, pos 14 -> 4 entries
                      [6, s, 7, 8, s, s],     # hole, pos 5 -> 2 entries
                      [9, 10, s, s, s, s],    # parked prefill -> 2 entries
                      [s, s, s, s, s, s],     # idle, parked -> 0
                      [s, s, s, s, s, s],     # idle at pos 0 -> 0
                      [0, 1, 2, 11, 12, 13]],  # full -> 6
                     np.int32)
    pos = np.array([9, 14, 5, nt * bs, nt * bs, 0, nt * bs - 1], np.int32)
    want = [3, 4, 2, 2, 0, 0, 6]
    np.testing.assert_array_equal(walk_bound(table, pos, bs, nb), want)
    np.testing.assert_array_equal(
        np.asarray(walk_bound(jnp.asarray(table), jnp.asarray(pos), bs, nb)),
        want)
    assert [pages_per_block(b, n) for b, n in
            [(16, 256), (64, 20), (1, 9), (1024, 4), (16, 3)]] == \
        [32, 8, 9, 1, 3]


# ---- in-kernel masking -------------------------------------------------------


def test_sentinel_block_inside_window_is_masked():
    """Defense in depth: a sentinel entry *below* ``pos`` (impossible for a
    live slot under the manager's reservation invariant, but exactly what
    a buggy host table would produce) is hard-masked by the kernel and the
    oracle alike, instead of attending whatever block the clamped fetch
    landed on."""
    q, kp, vp, table, pos = _make_case(19, batch=2, heads=4, kvh=2, hd=8,
                                       bs=4, n_table=4)
    n_blocks = kp.shape[1]
    table = table.at[0, 1].set(n_blocks)  # hole inside slot 0's window
    pos = pos.at[0].set(14)               # covers table entries 0..3
    y = paged_attention(q, kp, vp, table, pos)
    yr = paged_attention_ref(q, kp, vp, table, pos)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)
    # and the hole genuinely changed the result vs the unholed table
    y_full = paged_attention(q, kp, vp, table.at[0, 1].set(1), pos)
    assert not np.allclose(np.asarray(y)[0], np.asarray(y_full)[0])


def test_fully_masked_slot_emits_zeros_not_nan():
    """A released decode slot (all-sentinel table) must emit zeros via the
    guarded division — the dense path's softmax would give uniform weights
    over garbage; both engines ignore the row, but the kernel must not
    poison anything with NaN."""
    q, kp, vp, table, pos = _make_case(23, batch=2, heads=4, kvh=2, hd=8,
                                       bs=4, n_table=3)
    n_blocks = kp.shape[1]
    table = table.at[1].set(n_blocks)
    y = paged_attention(q, kp, vp, table, pos)
    yr = paged_attention_ref(q, kp, vp, table, pos)
    assert np.isfinite(np.asarray(y)).all()
    assert (np.asarray(y)[1] == 0.0).all()
    np.testing.assert_array_equal(np.asarray(y)[1], np.asarray(yr)[1])
    # slot 0 is untouched by slot 1's masking
    np.testing.assert_allclose(
        np.asarray(y)[0],
        _dense_gather_attend(q, kp, vp, table, pos)[0], atol=1e-5, rtol=1e-5)


def test_mask_fill_constant_matches_attention_layer():
    """nn keeps its own NEG_INF literal (it must not eagerly import the
    pallas stack); this pins it to the kernels/oracle value so the paged
    bit-identity contract cannot drift apart silently."""
    from repro.kernels.ref import NEG_INF as kernel_fill
    from repro.nn.attention import NEG_INF as attn_fill

    assert kernel_fill == attn_fill == NEG_INF


def test_kernel_validates_shapes():
    q, kp, vp, table, pos = _make_case(1, batch=2, heads=4, kvh=2, hd=8,
                                       bs=4, n_table=3)
    with pytest.raises(ValueError, match="kv_heads"):
        paged_attention(q[:, :3], kp, vp, table, pos)  # 3 % 2 != 0
    with pytest.raises(ValueError, match="mismatch"):
        paged_attention(q, kp, vp[:, :, :, :4], table, pos)
    with pytest.raises(ValueError, match="batch"):
        paged_attention(q, kp, vp, table, pos[:1])


# ---- serving identity through ContinuousEngine -------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-tiny").reduced()  # GQA: 4 heads over 2 KV heads
    model = build_model(jax.random.PRNGKey(0), cfg)
    return model, cfg


def test_engine_pallas_bit_identical_on_shared_prefix_trace(setup):
    """Acceptance gate: greedy decode through ContinuousEngine with
    decode_kernel='pallas' (interpret mode on CPU) is bit-identical to the
    dense-gather reference path on a seeded shared-prefix trace —
    staggered arrivals, slot recycling, prefix-cache hits and all."""
    model, cfg = setup
    trace = make_trace(10, seed=13, load=0.7, min_prompt=2, max_prompt=10,
                       min_new=2, max_new=8, vocab=cfg.vocab,
                       shared_prefix=6)
    outs = {}
    for dk in ("reference", "pallas"):
        eng = ContinuousEngine(model, cfg, batch=3, max_len=32,
                               max_prompt_len=16, kv_layout="paged",
                               block_size=4, decode_kernel=dk)
        outs[dk], _ = replay(eng, trace)
        assert eng.kv_stats()["decode_kernel"] == dk
        assert eng.manager.fully_free
    assert len(outs["pallas"]) == len(trace)
    for cr, cp in zip(outs["reference"], outs["pallas"]):
        assert cr.tokens == cp.tokens, \
            f"pallas decode diverged for uid={cr.uid} plen={cr.prompt_len}"
        assert (cr.uid, cr.prompt_len, cr.finish_reason) == \
            (cp.uid, cp.prompt_len, cp.finish_reason)


def test_engine_pallas_cache_full_frozen_slot(setup):
    """The eviction-frozen-slot path from PR 2 under the fused kernel: a
    slot frozen at pos == max_len keeps writing nowhere and its (ignored)
    attention output never corrupts a live neighbor."""
    model, cfg = setup
    rng = np.random.default_rng(7)
    long_lived = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    cache_filler = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    outs = {}
    for dk in ("reference", "pallas"):
        eng = ContinuousEngine(model, cfg, batch=2, max_len=16,
                               max_prompt_len=8, kv_layout="paged",
                               block_size=4, decode_kernel=dk)
        eng.submit(long_lived, max_new_tokens=12)
        eng.submit(cache_filler, max_new_tokens=16)  # frozen at pos 16
        outs[dk] = {c.prompt_len: c for c in eng.run()}
    assert outs["pallas"][6].finish_reason == "cache_full"
    for plen in (4, 6):
        assert outs["pallas"][plen].tokens == outs["reference"][plen].tokens, \
            f"frozen cache-full slot corrupted prompt_len={plen}"


def test_engine_decode_kernel_validation(setup):
    model, cfg = setup
    with pytest.raises(ValueError, match="decode_kernel"):
        ContinuousEngine(model, cfg, batch=2, max_len=32, max_prompt_len=8,
                         decode_kernel="cuda")
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(model, cfg, batch=2, max_len=32, max_prompt_len=8,
                         kv_layout="dense", decode_kernel="pallas")
