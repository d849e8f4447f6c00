"""Pallas write of new K/V rows into the paged pool, in place.

The serving step programs carry the stacked paged pool ``(n_layers,
kv_heads, n_blocks, block_size, head_dim)`` whole through the layer scan
(:class:`repro.nn.attention.PagedKVCache`).  An XLA scatter into that
pool is not in place on the TPU: the scatter wants another tiling than
the attention kernels read, so the compiler re-tiles the whole pool on
the way in and back on the way out, in every layer.  This kernel writes
the rows without touching the rest of the pool: for each pool block a
step writes into, it DMAs the block ``[layer, kh, block]`` into VMEM,
puts the new rows in under a row mask, and DMAs the block back.  The
pools are aliased from input to output, so the program hands the same
buffer from the donated argument to the result.

Callers give the rows as runs of consecutive positions (a decode step's
``s`` rows of one slot, or one slot's prefill chunk), and
:func:`write_runs` cuts each run into whole-block segments: a run of
``n`` rows starting at row ``o`` of a block touches at most ``(n + bs -
2) // bs + 1`` blocks.  A segment's rows are written only where their
block is a real one; rows whose block is the sentinel ``n_blocks`` (one
past the pool) are dropped, as a ``mode="drop"`` scatter drops them: a
frozen slot at ``pos == max_table * bs``, chunk padding, cached-prefix
positions.  A segment's live rows are one contiguous span, ``[lo, hi)``
of its block, because the dropped rows of a run are a prefix (cached
prefix) and a suffix (padding, or positions past the table).

All block reads of a layer are started before the first is waited on,
then merged, then all writes started before the first is waited on.
Reads and writes of one call may not meet in a block, so no two
segments of one call may name the same block.  The engine's block
ownership guarantees that: only immutable full prompt blocks are shared
between slots (:mod:`repro.serve.paging`), while a prompt's partial last
block and every decode-time block is allocated fresh to one slot, and
one slot's table maps distinct entries to distinct blocks.

Under tensor parallelism the kernel runs shard-local
(``repro.nn.attention``, under ``shard_map``) on one shard's KV heads of
the pool; every data shard writes every slot's rows into its own replica
of the pool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret


def n_segments(n: int, block_size: int) -> int:
    """Blocks a run of ``n`` consecutive rows can touch, wherever in a
    block it starts."""
    return (n + block_size - 2) // block_size + 1


def _pool_write_kernel(layer_ref, block_ref, lo_ref, hi_ref,  # prefetch
                       kt_ref, vt_ref, k_in, v_in, k_out, v_out,
                       k_buf, v_buf, sems, *, n_blocks: int, n_seg: int):
    kh, layer = pl.program_id(0), layer_ref[0]

    def copies(g, into_vmem):
        bid = block_ref[g]
        pairs = ((k_in, k_out, k_buf), (v_in, v_out, v_buf))
        return [pltpu.make_async_copy(src.at[layer, kh, bid], buf.at[g],
                                      sems.at[i]) if into_vmem else
                pltpu.make_async_copy(buf.at[g], dst.at[layer, kh, bid],
                                      sems.at[i])
                for i, (src, dst, buf) in enumerate(pairs)]

    def each_live(into_vmem, wait):
        def body(g, carry):
            @pl.when(block_ref[g] < n_blocks)
            def _copy():
                for c in copies(g, into_vmem):
                    if wait:
                        c.wait()
                    else:
                        c.start()
            return carry

        jax.lax.fori_loop(0, n_seg, body, 0)

    def merge(g, carry):
        row = jax.lax.broadcasted_iota(jnp.int32, k_buf.shape[1:], 0)
        new = (row >= lo_ref[g]) & (row < hi_ref[g])
        k_buf[g] = jnp.where(new, kt_ref[0, g], k_buf[g])
        v_buf[g] = jnp.where(new, vt_ref[0, g], v_buf[g])
        return carry

    each_live(into_vmem=True, wait=False)
    each_live(into_vmem=True, wait=True)
    jax.lax.fori_loop(0, n_seg, merge, 0)
    each_live(into_vmem=False, wait=False)
    each_live(into_vmem=False, wait=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pool_write_call(k_pool, v_pool, layer, block, lo, hi, k_tiles, v_tiles,
                     *, interpret: bool):
    kvh, n_blocks, bs, hd = k_pool.shape[1:]
    n_seg = block.shape[0]
    tile = pl.BlockSpec((1, n_seg, bs, hd), lambda kh, *_: (kh, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(kvh,),
        in_specs=[tile, tile,
                  pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((n_seg, bs, hd), k_pool.dtype),
            pltpu.VMEM((n_seg, bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),            # k / v
        ],
    )
    return pl.pallas_call(
        functools.partial(_pool_write_kernel, n_blocks=n_blocks,
                          n_seg=n_seg),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # operands count the four scalar-prefetch arrays first
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, block, lo, hi, k_tiles, v_tiles, k_pool, v_pool)


def _segments(start, blk, n_blocks: int, bs: int, n_seg: int):
    """One run's segments: ``(block, lo, hi)``, each ``(n_seg,)``, and the
    run row each tile row takes, ``(n_seg * bs,)``."""
    n = blk.shape[0]
    o = start % bs
    j = jnp.arange(n)
    row = (o + j) % bs
    live = (((o + j) // bs)[None, :] == jnp.arange(n_seg)[:, None]) \
        & (blk < n_blocks)[None, :]
    block = jnp.where(live, blk[None, :], n_blocks).min(1)
    lo = jnp.where(live, row[None, :], bs).min(1)
    hi = jnp.where(live, row[None, :] + 1, 0).max(1)
    src = jnp.clip(jnp.arange(n_seg * bs) - o, 0, n - 1)
    return block, lo, hi, src


def write_runs(k_pool, v_pool, layer, start, blk, k_rows, v_rows, *,
               interpret: bool | None = None):
    """Write runs of new rows into layer ``layer`` of the pools, in place.

    k/v_pool: ``(n_layers, kv_heads, n_blocks, block_size, head_dim)``;
    layer: () int32; start: ``(R,)`` int32, the position of each run's
    first row; blk: ``(R, n)`` int32, the pool block of each row,
    ``n_blocks`` (or more) to drop it; k/v_rows: ``(R, n, kv_heads,
    head_dim)``.  Row ``j`` of run ``r`` lands in row ``(start[r] + j) %
    block_size`` of block ``blk[r, j]``.  Returns the two pools, which
    alias the ones passed in (donate them for a write in place).
    """
    if interpret is None:
        interpret = default_interpret()
    kvh, n_blocks, bs, hd = k_pool.shape[1:]
    runs, n = blk.shape
    n_seg = n_segments(n, bs)
    block, lo, hi, src = jax.vmap(
        lambda s, b: _segments(s, b, n_blocks, bs, n_seg))(start, blk)

    def tiles(rows, dtype):
        # (R, n, kvh, hd) -> (kvh, R * n_seg, bs, hd), tile row i of run r
        # holding run row src[r, i]
        t = jnp.take_along_axis(rows.astype(dtype), src[:, :, None, None],
                                axis=1)
        return t.reshape(runs, n_seg, bs, kvh, hd).transpose(
            3, 0, 1, 2, 4).reshape(kvh, runs * n_seg, bs, hd)

    return _pool_write_call(
        k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1),
        block.reshape(-1), lo.reshape(-1), hi.reshape(-1),
        tiles(k_rows, k_pool.dtype), tiles(v_rows, v_pool.dtype),
        interpret=interpret)


__all__ = ["n_segments", "write_runs"]
