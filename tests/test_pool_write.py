"""In-place Pallas write of new K/V rows into the paged pool.

``repro.kernels.pool_write`` writes a step's new rows into one layer of
the stacked pool by DMA; the XLA scatter it replaces is the oracle.
Every case runs the kernel in interpret mode (chosen by platform;
``tests/test_chip_compile.py`` compiles it for a described TPU) through
the attention layer's row write, and the pool must come out bit-identical
to the scatter's, in every layer.  The engine-level test checks the
ownership invariant the kernel relies on: no two slots of one decode
step write into the same pool block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.kernels.pool_write import n_segments, write_runs
from repro.models import build_model
from repro.nn.attention import PagedKVCache, _write_rows
from repro.serve import ContinuousEngine, make_trace, replay

LAYERS, KVH, HD = 3, 2, 8


def _pool(seed, n_blocks, bs, dtype):
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    shape = (LAYERS, KVH, n_blocks, bs, HD)
    return (jax.random.normal(kk, shape).astype(dtype),
            jax.random.normal(kv, shape).astype(dtype))


def _decode_blocks(table, pos, s, bs, n_blocks):
    """The blocks a decode step of ``s`` rows per slot writes, as
    ``Attention._decode_paged`` computes them."""
    qpos = pos[:, None] + np.arange(s)[None, :]
    n_table = table.shape[1]
    blk = np.take_along_axis(table, np.minimum(qpos // bs, n_table - 1), 1)
    return np.where(qpos < n_table * bs, blk, n_blocks)


def _chunk_blocks(table_row, offset, w, lo, hi, bs, n_blocks):
    """The blocks a chunk writes, as the engine's ``scatter_rows`` points
    them: positions outside ``[lo, hi)`` (cached prefix, padding) at the
    sentinel."""
    p = offset + np.arange(w)
    live = (p >= lo) & (p < hi)
    return np.where(live, table_row[np.minimum(p // bs, len(table_row) - 1)],
                    n_blocks)[None]


def _case(name):
    """``(n_blocks, bs, layer, start (R,), blk (R, n))`` of one case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "block_size_one":
        nb, bs = 40, 1
    else:
        nb, bs = 24, 4
    layer = 1
    perm = rng.permutation(nb).astype(np.int32)
    n_table = 5
    table = perm[:4 * n_table].reshape(4, n_table)
    if name == "decode_sentinel_rows":
        table[1] = nb                      # a released slot
        table[2, 2:] = nb                  # past slot 2's reservation
        pos = np.array([6, 3, 9, 17])
        return nb, bs, layer, pos, _decode_blocks(table, pos, 1, bs, nb)
    if name == "decode_frozen_slot":       # parked at max_table * bs
        pos = np.array([n_table * bs, 2, n_table * bs, 11])
        return nb, bs, 2, pos, _decode_blocks(table, pos, 1, bs, nb)
    if name == "verify_rows_span_block_edge":  # s > 1, speculative verify
        pos = np.array([2, 7, 0, n_table * bs - 2])
        return nb, bs, layer, pos, _decode_blocks(table, pos, 3, bs, nb)
    if name == "chunk_spans_block_edges":
        return nb, bs, layer, np.array([3]), _chunk_blocks(
            table[0], 3, 8, 3, 11, bs, nb)
    if name == "chunk_prefix_resume_mid_block":
        # the prompt's first 6 positions are a cached prefix; the chunk
        # resumes at 5 (mid-block), its first row dropped, padding at the end
        return nb, bs, 0, np.array([5]), _chunk_blocks(
            table[1], 5, 8, 6, 10, bs, nb)
    if name == "block_size_one":
        pos = np.array([0, 3, n_table, 2])
        return nb, bs, layer, pos, _decode_blocks(table, pos, 2, bs, nb)
    raise KeyError(name)


CASES = ["decode_sentinel_rows", "decode_frozen_slot",
         "verify_rows_span_block_edge", "chunk_spans_block_edges",
         "chunk_prefix_resume_mid_block", "block_size_one"]


def _check(n_blocks, bs, layer, start, blk, dtype, seed=0):
    kp, vp = _pool(seed, n_blocks, bs, dtype)
    runs, n = blk.shape
    kr, vr = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (2, runs, n, KVH, HD))
    cache = PagedKVCache(kp, vp, jnp.zeros((1, 1), jnp.int32),
                         jnp.zeros((1,), jnp.int32))
    args = (cache, jnp.int32(layer), jnp.asarray(start, jnp.int32),
            jnp.asarray(blk, jnp.int32), kr, vr)
    out = {kern: _write_rows(*args, kernel=kern)
           for kern in ("reference", "pallas")}
    for i, name in enumerate("kv"):
        ref = np.asarray(out["reference"][i])
        got = np.asarray(out["pallas"][i])
        assert got.dtype == ref.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(got, ref, err_msg=name)
        before = np.asarray((kp, vp)[i])
        # only [layer] changed, and only where a row was written
        np.testing.assert_array_equal(np.delete(got, layer, 0),
                                      np.delete(before, layer, 0))
        n_written = int((blk < n_blocks).sum())
        changed = (got[layer] != before[layer]).any(-1).sum()
        assert changed == n_written * KVH, (changed, n_written)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", CASES)
def test_pool_write_matches_scatter(name, dtype):
    """Bit-exact against the XLA scatter on every layer of the pool:
    sentinel rows (a released slot, positions past a reservation) and a
    slot frozen at ``pos == max_table * bs`` write nothing, chunk rows
    cross block edges, a prefix-hit resume starts mid-block with its
    cached rows dropped, speculative-verify runs of several rows, and
    one-row blocks."""
    _check(*_case(name), dtype)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_pool_write_random_runs(seed):
    """Property: random runs (lengths, starts, live spans, block sizes)
    write what the scatter writes."""
    rng = np.random.default_rng(seed)
    bs = int(rng.integers(1, 6))
    runs, n = int(rng.integers(1, 4)), int(rng.integers(1, 9))
    n_seg = n_segments(n, bs)
    nb = runs * n_seg + int(rng.integers(0, 4))
    perm = rng.permutation(nb)
    start = rng.integers(0, 4 * bs, runs)
    blk = np.empty((runs, n), np.int64)
    for r in range(runs):  # distinct blocks per segment, as tables give
        seg = (start[r] % bs + np.arange(n)) // bs
        blk[r] = perm[r * n_seg + seg]
        lo, hi = sorted(rng.integers(0, n + 1, 2))
        blk[r, :lo], blk[r, hi:] = nb, nb
    _check(nb, bs, int(rng.integers(0, LAYERS)), start, blk,
           jnp.float32, seed=seed)


def test_segments_cover_each_run():
    """A run of ``n`` rows touches at most ``n_segments(n, bs)`` blocks
    wherever it starts, and the write kernel is told each block once."""
    for bs in (1, 4, 16):
        for n in (1, 3, 16, 17, 256):
            worst = max(((o + n - 1) // bs) + 1 for o in range(bs))
            assert n_segments(n, bs) == worst


def test_write_runs_donates_in_place():
    """The pools come back in the buffers they went in with when donated
    (the kernel aliases them input to output)."""
    kp, vp = _pool(0, 8, 4, jnp.float32)
    ptr = kp.unsafe_buffer_pointer()
    write = jax.jit(write_runs, donate_argnums=(0, 1))
    k2, _ = write(kp, vp, jnp.int32(1), jnp.array([1], jnp.int32),
                  jnp.array([[3, 3]], jnp.int32), jnp.ones((1, 2, KVH, HD)),
                  jnp.ones((1, 2, KVH, HD)))
    assert k2.unsafe_buffer_pointer() == ptr


def test_no_two_slots_decode_into_one_block():
    """The write kernel reads every block of a step before it writes any,
    so two slots of one decode step must never write the same block.
    Only immutable full prompt blocks are shared between slots; a
    prompt's partial last block and every decode-time block are a slot's
    own.  Checked on every decode step of a shared-prefix trace with
    prefix hits, slot recycling and cache-full freezing."""
    cfg = get_config("paper-tiny").reduced()
    model = build_model(jax.random.PRNGKey(0), cfg)
    trace = make_trace(12, seed=5, load=0.8, min_prompt=2, max_prompt=10,
                       min_new=2, max_new=14, vocab=cfg.vocab,
                       shared_prefix=8)
    eng = ContinuousEngine(model, cfg, batch=4, max_len=26,
                           max_prompt_len=18, kv_layout="paged",
                           block_size=4, chunk_size=4,
                           decode_kernel="pallas", prefill_kernel="pallas")
    decode, steps = eng._decode, []

    def checked(models, cache, state, key):
        table = np.asarray(cache.table)
        pos = np.asarray(cache.length[0])
        nb, bs = cache.k.shape[2:4]
        blk = _decode_blocks(table, pos, 1, bs, nb)[:, 0]
        live = blk[blk < nb]
        assert len(set(live.tolist())) == len(live), (table, pos)
        steps.append(len(live))
        return decode(models, cache, state, key)

    eng._decode = checked
    done, _ = replay(eng, trace)
    assert len(done) == len(trace)
    assert any(c.finish_reason == "cache_full" for c in done)
    assert max(steps) >= 2  # steps where several slots wrote at once
    assert eng.kv_stats()["prefix_hit_tokens"] > 0
