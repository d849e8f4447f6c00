"""Flash prefill-chunk Pallas kernel: parity + serving identity.

Mirrors ``tests/test_paged_attention.py`` for the prefill side of the
kernel matrix (interpret mode on CPU, chosen by platform):

* kernel vs oracle — :func:`repro.kernels.chunk_attention` must match
  the pure-jnp :func:`repro.kernels.ref.chunk_attention_ref` across
  chunk-boundary, sub-chunk-prompt, mid-block prefix-hit-resume, and
  GQA/MQA cases.  Property-based via the ``tests/_hyp`` shim.
* layer three-way — ``Attention.prefill_chunk`` with
  ``prefill_kernel="pallas"`` matches its own reference gather on the
  valid rows (padding rows carry no contract but must stay finite) and
  writes bit-identical K/V, on BOTH the paged and the dense layout.
* serving identity — greedy tokens through ``ContinuousEngine`` with
  ``prefill_kernel="pallas"`` are bit-identical to the reference path
  on a seeded shared-prefix trace (prefix-cache hits resume mid-block),
  on both layouts.
* structured refusal — ring/ssm/hybrid cache kinds refuse the kernel
  the same way the decode-kernel guard does (``UnsupportedCacheError``
  with a roadmap pointer at the engine, ``NotImplementedError`` at the
  ring layer).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.kernels import (chunk_attention, chunk_attention_dense,
                           chunk_attention_ref)
from repro.models import build_model
from repro.nn.attention import Attention, KVCache, PagedKVCache
from repro.serve import ContinuousEngine, make_trace, replay
from repro.serve.engine import UnsupportedCacheError


# ---- case construction -------------------------------------------------------


def _make_case(seed, *, heads, kvh, hd, bs, n_table, w, offset, n_valid,
               extra_blocks=2, dtype=jnp.float32):
    """One slot mid-prefill: a resident prefix of ``offset`` tokens behind
    a random block table (sentinel tail past the reservation), plus a
    ``w``-wide chunk whose first ``n_valid`` rows are real."""
    rng = np.random.default_rng(seed)
    n_blocks = n_table + extra_blocks
    kq, kk, kv, kc, kw = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (w, heads, hd), dtype)
    k_pool = jax.random.normal(kk, (kvh, n_blocks, bs, hd), dtype)
    v_pool = jax.random.normal(kv, (kvh, n_blocks, bs, hd), dtype)
    k_chunk = jax.random.normal(kc, (w, kvh, hd), dtype)
    v_chunk = jax.random.normal(kw, (w, kvh, hd), dtype)
    need = -(-(offset + n_valid) // bs) if offset + n_valid else 0
    table = np.full((n_table,), n_blocks, np.int32)
    table[:need] = rng.permutation(n_blocks)[:need]
    return (q, k_pool, v_pool, jnp.asarray(table), k_chunk, v_chunk,
            jnp.int32(offset), jnp.int32(n_valid))


def _assert_parity(case, tol=1e-5):
    q, *_ = case
    n_valid = int(case[-1])
    y = chunk_attention(*case)
    yr = chunk_attention_ref(*case)
    assert y.shape == yr.shape == q.shape
    assert y.dtype == q.dtype
    # the contract covers the valid rows; padding rows are never read by
    # the engine but must not poison anything with NaN/inf
    np.testing.assert_allclose(np.asarray(y, np.float32)[:n_valid],
                               np.asarray(yr, np.float32)[:n_valid],
                               atol=tol, rtol=tol, err_msg="kernel vs ref")
    assert np.isfinite(np.asarray(y)).all()


# ---- kernel vs oracle --------------------------------------------------------


@pytest.mark.parametrize("heads,kvh", [(4, 4), (4, 2), (4, 1), (1, 1)])
def test_parity_head_ratios(heads, kvh):
    """MHA, GQA, and MQA through the same kernel."""
    _assert_parity(_make_case(7, heads=heads, kvh=kvh, hd=16, bs=4,
                              n_table=5, w=8, offset=6, n_valid=8))


def test_parity_chunk_boundary():
    """offset a multiple of block_size AND of the chunk width — the
    admission pipeline's steady state."""
    _assert_parity(_make_case(11, heads=4, kvh=2, hd=8, bs=4, n_table=6,
                              w=4, offset=8, n_valid=4))


def test_parity_first_chunk():
    """offset == 0: no resident prefix, purely in-chunk causal."""
    _assert_parity(_make_case(13, heads=4, kvh=2, hd=8, bs=4, n_table=4,
                              w=8, offset=0, n_valid=8))


def test_parity_sub_chunk_prompt():
    """n_valid < W: a short prompt right-padded into the bucket; the
    padded rows must not perturb the valid ones."""
    _assert_parity(_make_case(17, heads=4, kvh=2, hd=8, bs=4, n_table=4,
                              w=8, offset=0, n_valid=3))


def test_parity_prefix_hit_resume_mid_block():
    """offset NOT a multiple of block_size — exactly where prefix-aware
    admission resumes after a cached-prefix hit (the final shared block
    is recomputed from its last token)."""
    _assert_parity(_make_case(19, heads=4, kvh=2, hd=8, bs=4, n_table=6,
                              w=8, offset=7, n_valid=8))


def test_parity_single_valid_row_and_block_size_one():
    _assert_parity(_make_case(23, heads=2, kvh=1, hd=8, bs=1, n_table=8,
                              w=4, offset=5, n_valid=1))


def test_parity_bf16_pool():
    _assert_parity(_make_case(3, heads=4, kvh=2, hd=16, bs=4, n_table=4,
                              w=8, offset=6, n_valid=8,
                              dtype=jnp.bfloat16), tol=2e-2)


def test_fully_padded_chunk_emits_finite():
    """n_valid == 0 (no real rows at all): every query row is fully
    masked — the guarded division must emit zeros, not NaN."""
    case = _make_case(29, heads=4, kvh=2, hd=8, bs=4, n_table=4, w=4,
                      offset=0, n_valid=0)
    y = np.asarray(chunk_attention(*case))
    assert np.isfinite(y).all() and (y == 0.0).all()


def test_sentinel_hole_in_prefix_is_masked():
    """A sentinel table entry *inside* the resident prefix (a buggy host
    table) is hard-masked by kernel and oracle alike."""
    q, kp, vp, table, kc, vc, off, nv = _make_case(
        31, heads=4, kvh=2, hd=8, bs=4, n_table=4, w=4, offset=12,
        n_valid=4)
    table = table.at[1].set(kp.shape[1])  # hole at positions 4..7
    case = (q, kp, vp, table, kc, vc, off, nv)
    _assert_parity(case)
    # and the hole genuinely changed the answer
    y_holed = chunk_attention(*case)
    y_full = chunk_attention(q, kp, vp, table.at[1].set(0), kc, vc, off, nv)
    assert not np.allclose(np.asarray(y_holed), np.asarray(y_full))


def test_dense_wrapper_matches_identity_table_oracle():
    """chunk_attention_dense pads the lane to a block multiple and serves
    it through an identity table; parity against the oracle on the same
    synthetic pool."""
    rng = jax.random.PRNGKey(5)
    kq, kl, kv2, kc, kw = jax.random.split(rng, 5)
    w, heads, kvh, hd, max_len, off = 6, 4, 2, 8, 21, 9
    q = jax.random.normal(kq, (w, heads, hd))
    k_lane = jax.random.normal(kl, (max_len, kvh, hd))
    v_lane = jax.random.normal(kv2, (max_len, kvh, hd))
    kc_ = jax.random.normal(kc, (w, kvh, hd))
    vc_ = jax.random.normal(kw, (w, kvh, hd))
    y = chunk_attention_dense(q, k_lane, v_lane, kc_, vc_,
                              jnp.int32(off), jnp.int32(w), block_size=8)
    bs = 8
    pad = -max_len % bs
    pool = jnp.pad(k_lane, ((0, pad), (0, 0), (0, 0)))
    poolv = jnp.pad(v_lane, ((0, pad), (0, 0), (0, 0)))
    n_table = (max_len + pad) // bs
    table = jnp.arange(n_table, dtype=jnp.int32)
    yr = chunk_attention_ref(q, pool.reshape(n_table, bs, kvh, hd)
                             .transpose(2, 0, 1, 3),
                             poolv.reshape(n_table, bs, kvh, hd)
                             .transpose(2, 0, 1, 3), table,
                             kc_, vc_, jnp.int32(off), jnp.int32(w))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-5)


def test_kernel_validates_shapes():
    q, kp, vp, table, kc, vc, off, nv = _make_case(
        1, heads=4, kvh=2, hd=8, bs=4, n_table=3, w=4, offset=4, n_valid=4)
    with pytest.raises(ValueError, match="kv_heads"):
        chunk_attention(q[:, :3], kp, vp, table, kc, vc, off, nv)
    with pytest.raises(ValueError, match="mismatch"):
        chunk_attention(q, kp, vp[:, :, :, :4], table, kc, vc, off, nv)
    with pytest.raises(ValueError, match="chunk"):
        chunk_attention(q, kp, vp, table, kc[:2], vc, off, nv)


@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=15, deadline=None)
def test_parity_random_cases(seed):
    """Property: random chunk widths, offsets (block-aligned and not),
    ragged n_valid, head ratios, block sizes, sentinel tails."""
    rng = np.random.default_rng(seed)
    heads, kvh = [(1, 1), (2, 1), (4, 2), (4, 4), (6, 3)][
        int(rng.integers(0, 5))]
    bs = int(rng.integers(1, 9))
    n_table = int(rng.integers(1, 7))
    w = int(rng.integers(1, 9))
    cap = n_table * bs
    offset = int(rng.integers(0, max(cap - w, 0) + 1))
    n_valid = int(rng.integers(0, min(w, cap - offset) + 1))
    _assert_parity(_make_case(
        int(rng.integers(0, 2**31)), heads=heads, kvh=kvh,
        hd=int(rng.choice([4, 8, 16])), bs=bs, n_table=n_table, w=w,
        offset=offset, n_valid=n_valid,
        extra_blocks=int(rng.integers(0, 4))))


# ---- layer-level three-way: Attention.prefill_chunk --------------------------

DIM, HEADS, KVH, HD = 32, 4, 2, 8
LAYERS, LAYER = 3, 1  # the layer tests write layer 1 of a 3-layer pool


def _layer():
    return Attention.create(jax.random.PRNGKey(7), DIM, HEADS, KVH,
                            head_dim=HD, dtype=jnp.float32)


def _paged_cache(batch, n_blocks, bs, n_table, n_layers=LAYERS):
    """Every layer's pool, as the layer scan hands it to each layer, with
    one layer's lengths."""
    return PagedKVCache(
        k=jnp.zeros((n_layers, KVH, n_blocks, bs, HD)),
        v=jnp.zeros((n_layers, KVH, n_blocks, bs, HD)),
        table=jnp.full((batch, n_table), n_blocks, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32))


def _dst(table_row, off, w, n_valid, bs, n_blocks):
    """Engine-style pool rows for the chunk: real rows through the block
    table, padding rows at the out-of-range sentinel (dropped)."""
    j = np.arange(w)
    p = off + j
    rows = np.asarray(table_row)[p // bs] * bs + p % bs
    return jnp.asarray(np.where(j < n_valid, rows, n_blocks * bs))


def _scan_paged(attn, cache, x, slot, chunk, kernel):
    """Feed x (1, plen, dim) through paged prefill_chunk in chunk-sized
    spans (engine-style: blocks allocated up front here)."""
    plen, bs = x.shape[1], cache.k.shape[3]
    outs = []
    for off in range(0, plen, chunk):
        n = min(chunk, plen - off)
        span = x[:, off:off + chunk]
        if span.shape[1] < chunk:
            span = jnp.pad(span, ((0, 0), (0, chunk - span.shape[1]),
                                  (0, 0)))
        out, cache = attn.prefill_chunk(
            span, cache, slot=jnp.int32(slot), offset=jnp.int32(off),
            n_valid=jnp.int32(n),
            dst=_dst(cache.table[slot], off, chunk, n, bs,
                     cache.k.shape[2]),
            prefill_kernel=kernel, layer=jnp.int32(LAYER))
        outs.append(out[:, :n])
    return jnp.concatenate(outs, axis=1), cache


def test_layer_paged_pallas_matches_reference_multichunk():
    """Three-way at the layer: the pallas path of Attention.prefill_chunk
    equals its own reference gather on every valid row across a chunked
    scan, and the written K/V pool is bit-identical (the in-place DMA
    write against the XLA scatter), in the one layer it names."""
    attn = _layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, DIM))
    bs, n_table = 4, 4
    results = {}
    for kernel in ("reference", "pallas"):
        cache = _paged_cache(2, 9, bs, n_table)
        cache = cache._replace(
            table=cache.table.at[1, :n_table].set(
                jnp.asarray([5, 2, 7, 0], jnp.int32)))
        results[kernel] = _scan_paged(attn, cache, x, slot=1, chunk=4,
                                      kernel=kernel)
    out_r, cache_r = results["reference"]
    out_p, cache_p = results["pallas"]
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(cache_p.k),
                                  np.asarray(cache_r.k))
    np.testing.assert_array_equal(np.asarray(cache_p.v),
                                  np.asarray(cache_r.v))
    np.testing.assert_array_equal(np.asarray(cache_p.length),
                                  np.asarray(cache_r.length))
    others = np.delete(np.asarray(cache_p.k), LAYER, axis=0)
    assert not others.any() and np.asarray(cache_p.k)[LAYER].any()


def test_layer_dense_pallas_matches_reference():
    """Same three-way on the dense per-slot layout (no block table: the
    kernel sees the lane through an identity table)."""
    attn = _layer()
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, DIM))
    results = {}
    for kernel in ("reference", "pallas"):
        cache = KVCache.zeros(2, 21, KVH, HD, dtype=jnp.float32,
                              per_slot=True)
        out1, cache = attn.prefill_chunk(
            x[:, :4], cache, slot=jnp.int32(0), offset=jnp.int32(0),
            n_valid=jnp.int32(4), prefill_kernel=kernel)
        out2, cache = attn.prefill_chunk(
            x[:, 4:], cache, slot=jnp.int32(0), offset=jnp.int32(4),
            n_valid=jnp.int32(3), prefill_kernel=kernel)  # ragged tail
        results[kernel] = (jnp.concatenate([out1, out2[:, :3]], 1), cache)
    out_r, cache_r = results["reference"]
    out_p, cache_p = results["pallas"]
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(cache_p.k),
                                  np.asarray(cache_r.k))


def test_layer_validates_kernel_name():
    attn = _layer()
    cache = KVCache.zeros(1, 8, KVH, HD, dtype=jnp.float32, per_slot=True)
    with pytest.raises(ValueError, match="prefill_kernel"):
        attn.prefill_chunk(jnp.zeros((1, 4, DIM)), cache,
                           slot=jnp.int32(0), offset=jnp.int32(0),
                           n_valid=jnp.int32(4), prefill_kernel="cuda")


def test_ring_layer_refuses_pallas():
    """Ring lanes wrap around — no position-addressable prefix, so the
    layer refuses the kernel outright instead of silently falling back."""
    attn = Attention.create(jax.random.PRNGKey(7), DIM, HEADS, KVH,
                            head_dim=HD, window=4, dtype=jnp.float32)
    cache = KVCache.zeros(1, 4, KVH, HD, dtype=jnp.float32, per_slot=True)
    with pytest.raises(NotImplementedError, match="ring"):
        attn.prefill_chunk(jnp.zeros((1, 4, DIM)), cache,
                           slot=jnp.int32(0), offset=jnp.int32(0),
                           n_valid=jnp.int32(4), prefill_kernel="pallas")


# ---- serving identity through ContinuousEngine -------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("paper-tiny").reduced()  # GQA: 4 heads over 2 KV heads
    model = build_model(jax.random.PRNGKey(0), cfg)
    return model, cfg


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_engine_pallas_prefill_bit_identical(setup, kv_layout):
    """Acceptance gate: greedy tokens with prefill_kernel='pallas' are
    bit-identical to the reference path on a seeded shared-prefix trace
    (chunked admission, prefix-cache hits resuming mid-block on the
    paged layout), on BOTH kv layouts."""
    model, cfg = setup
    trace = make_trace(8, seed=13, load=0.7, min_prompt=2, max_prompt=8,
                       min_new=2, max_new=8, vocab=cfg.vocab,
                       shared_prefix=6)
    outs = {}
    for pk in ("reference", "pallas"):
        eng = ContinuousEngine(model, cfg, batch=3, max_len=32,
                               max_prompt_len=16, kv_layout=kv_layout,
                               block_size=4, chunk_size=4,
                               prefill_chunk_budget=4, prefill_kernel=pk)
        outs[pk], _ = replay(eng, trace)
        assert eng.prefill_stats()["prefill_kernel"] == pk
        if kv_layout == "paged":
            assert eng.kv_stats()["prefill_kernel"] == pk
    assert len(outs["pallas"]) == len(trace)
    for cr, cp in zip(outs["reference"], outs["pallas"]):
        assert cr.tokens == cp.tokens, \
            f"pallas prefill diverged for uid={cr.uid} plen={cr.prompt_len}"
        assert (cr.uid, cr.prompt_len, cr.finish_reason) == \
            (cp.uid, cp.prompt_len, cp.finish_reason)


def test_engine_prefill_kernel_validation(setup):
    """Unknown names rejected; ring (hymba-style window) and ssm cache
    kinds refuse with the structured error + roadmap pointer, mirroring
    the decode-kernel guard."""
    model, cfg = setup
    with pytest.raises(ValueError, match="prefill_kernel"):
        ContinuousEngine(model, cfg, batch=2, max_len=32, max_prompt_len=8,
                         prefill_kernel="cuda")
    ring_cfg = cfg.replace(window=8)
    ring_model = build_model(jax.random.PRNGKey(0), ring_cfg)
    with pytest.raises(UnsupportedCacheError) as ei:
        ContinuousEngine(ring_model, ring_cfg, batch=2, max_len=32,
                         max_prompt_len=8, chunk_size=8,
                         prefill_kernel="pallas")
    assert ei.value.roadmap_item
    mb_cfg = get_config("mamba2-2.7b").reduced()
    mb_model = build_model(jax.random.PRNGKey(0), mb_cfg)
    with pytest.raises(UnsupportedCacheError, match="kv"):
        ContinuousEngine(mb_model, mb_cfg, batch=2, max_len=32,
                         max_prompt_len=8, prefill_kernel="pallas")
