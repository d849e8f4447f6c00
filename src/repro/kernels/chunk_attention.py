"""Flash-style Pallas prefill-chunk attention kernel.

One prompt chunk of ``W`` bucket-padded tokens for ONE serving slot
attends against (a) the slot's resident KV prefix — everything below
``offset``, streamed block-by-block from one layer of the paged pool
through the slot's block table — and (b) the chunk's own fresh K/V rows
under an in-chunk causal + padding mask.  This is exactly the attention
:meth:`repro.nn.attention.Attention.prefill_chunk` computes for its
valid rows, minus the dense gather: the reference path first
materializes the whole ``(max_table * block_size, kv_heads, head_dim)``
logical lane per chunk (a full HBM round-trip of the slot's cache for
every chunk of every prompt), while here prefix blocks stream through
VMEM inside an online-softmax loop and the gathered view never exists.

Grid layout: ``(kh over KV heads, i over table entries + 1)``, both
sequential ("arbitrary") so the per-``kh`` running max / sum /
accumulator scratch persists across the ``i`` steps:

  * ``i == 0``: zero the online-softmax carry.
  * ``i < n_table``: fetch pool block ``table[i]`` (sentinel entries are
    clamped to a real row for the DMA and masked in-kernel) and
    accumulate the prefix half under ``kpos < offset`` — strictly below
    the chunk, so the mask needs no per-query term (``kpos < offset <=
    qpos`` for every chunk row).  Blocks entirely at/past ``offset``
    are skipped (``pl.when``), so a short prefix pays for the blocks it
    has, not for ``max_table``.
  * ``i == n_table``: accumulate the chunk's fresh K/V under the
    offset-relative causal + padding mask ``(j <= r) & (j < n_valid)``
    (query row ``r`` sits at absolute position ``offset + r``), then
    emit the normalized output.

Padding rows (``r >= n_valid``) attend only the prefix and their own
in-chunk causal span — NOT whatever stale pool bytes the reference
gather happens to see past the write frontier — so their outputs differ
from the reference; they are discarded by construction (the engine
samples only the last *valid* row's logits, and padding rows' K/V
scatter to the drop sentinel).  Parity is asserted on rows
``< n_valid``, and a fully-masked row emits zeros via the guarded
division rather than NaN.

GQA/MQA fall out of the layout: ``q`` is reshaped to ``(kv_heads,
W * group, head_dim)`` (row ``r = w * group + g``) and each grid step
attends one KV head's query block.  The pool is the stacked pool of every
layer, head-major, ``(n_layers, kv_heads, n_blocks, block_size,
head_dim)``, so every fetched block is ``(block_size, head_dim)`` in its
last two dims, as the TPU compiler's tiling requires (see
:mod:`repro.kernels.paged_attention`).  The layer to read rides in the
scalar-prefetch ``meta`` operand beside the offset and the valid count,
and the pool's block index map reads it, so the layer scan hands the
kernel the whole pool and no layer slice is ever cut out of it.  Under
tensor parallelism it runs shard-local on one shard's KV heads and their
query groups, like the decode kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret
from repro.kernels.ref import NEG_INF  # one mask fill value, kernel == oracle


def _chunk_attn_kernel(table_ref, meta_ref,  # scalar prefetch
                       q_ref, kp_ref, vp_ref, kc_ref, vc_ref, o_ref,
                       m_ref, l_ref, acc_ref, *,
                       block_size: int, n_blocks: int, n_table: int,
                       group: int):
    i = pl.program_id(1)
    off = meta_ref[0]
    n_valid = meta_ref[1]
    q = q_ref[0].astype(jnp.float32)            # (W*group, head_dim)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(k, v, valid):
        """One online-softmax step over ``k``/``v``: (L, hd), valid (Wg, L)."""
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_ref[...]                     # (Wg, 1)
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        # the explicit where (not just the NEG_INF fill) matters: while
        # every key so far is masked, m_new == NEG_INF and
        # exp(logits - m_new) would be exp(0) == 1 on the masked lanes
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # prefix half: resident pool blocks, strictly below the chunk.  Blocks
    # at/past the offset hold nothing this chunk may attend — skip them.
    @pl.when((i < n_table) & (i * block_size < off))
    def _prefix():
        k = kp_ref[0, 0, 0].astype(jnp.float32)  # (block_size, head_dim)
        v = vp_ref[0, 0, 0].astype(jnp.float32)
        bid = table_ref[jnp.minimum(i, n_table - 1)]
        kpos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = (kpos < off) & (bid != n_blocks)  # (1, bs) -> broadcast
        accumulate(k, v, jnp.broadcast_to(valid, (m_ref.shape[0],
                                                  block_size)))

    # chunk half: the fresh K/V under the in-chunk causal + padding mask,
    # then emit (last grid step, carry complete)
    @pl.when(i == n_table)
    def _chunk():
        k = kc_ref[0].astype(jnp.float32)        # (W, head_dim)
        v = vc_ref[0].astype(jnp.float32)
        wg, w = m_ref.shape[0], k.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (wg, w), 0) // group
        j = jax.lax.broadcasted_iota(jnp.int32, (wg, w), 1)
        accumulate(k, v, (j <= r) & (j < n_valid))
        # guarded division: a fully-masked row (offset == 0 padding row
        # attending nothing) emits zeros, not NaN
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_attention_call(q3, k_pool, v_pool, k_chunk3, v_chunk3, table_row,
                          meta, *, interpret: bool):
    kvh, wg, hd = q3.shape
    n_blocks, bs = k_pool.shape[2:4]
    w = k_chunk3.shape[1]
    n_table = table_row.shape[0]

    def kv_map(kh, i, table_ref, meta_ref):
        # sentinel entries (n_blocks, one past the pool) are clamped to a
        # real block for the fetch; the kernel masks their lanes to zero.
        # The final grid step (the chunk half) never reads the pool refs —
        # clamp its index into range for the prefetch DMA.
        safe_i = jnp.minimum(i, n_table - 1)
        return (meta_ref[2], kh, jnp.minimum(table_ref[safe_i], n_blocks - 1),
                0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(kvh, n_table + 1),
        in_specs=[
            pl.BlockSpec((1, wg, hd), lambda kh, i, t, m: (kh, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, 1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, w, hd), lambda kh, i, t, m: (kh, 0, 0)),
            pl.BlockSpec((1, w, hd), lambda kh, i, t, m: (kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, wg, hd), lambda kh, i, t, m: (kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((wg, 1), jnp.float32),   # running max
            pltpu.VMEM((wg, 1), jnp.float32),   # running sum
            pltpu.VMEM((wg, hd), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_chunk_attn_kernel, block_size=bs,
                          n_blocks=n_blocks, n_table=n_table,
                          group=wg // w),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, wg, hd), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(table_row, meta, q3, k_pool, v_pool, k_chunk3, v_chunk3)


def chunk_attention(
    q: jax.Array,          # (W, heads, head_dim) — one slot's chunk queries
    k_pool: jax.Array,     # ([layers,] kv_heads, n_blocks, block_size, hd)
    v_pool: jax.Array,     # ([layers,] kv_heads, n_blocks, block_size, hd)
    table_row: jax.Array,  # (max_table,) int32 — ONE slot's block table
    k_chunk: jax.Array,    # (W, kv_heads, head_dim) — the chunk's fresh K
    v_chunk: jax.Array,    # (W, kv_heads, head_dim)
    offset: jax.Array,     # () int32 — absolute position of chunk row 0
    n_valid: jax.Array,    # () int32 — real (non-padding) rows in the chunk
    layer: jax.Array | int = 0,  # () int32 — the pool layer to read
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused prefill-chunk attention against layer ``layer`` of the paged
    KV pool (a pool without the layer dim is one layer).

    Returns ``(W, heads, head_dim)`` in ``q.dtype``.  Rows ``< n_valid``
    match :func:`repro.kernels.ref.chunk_attention_ref` and the dense
    gather in :meth:`repro.nn.attention.Attention.prefill_chunk` (same
    masking, fp32 accumulation; vs the gather the only difference is
    online-softmax float ordering).  Rows ``>= n_valid`` are padding and
    carry no contract.  ``interpret=None`` auto-selects interpret mode
    off-TPU (see :func:`repro.kernels.ops.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    w, heads, hd = q.shape
    kvh, n_blocks, bs, hd_k = k_pool.shape[1:]
    if hd_k != hd or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool/query shape mismatch: q {q.shape}, k {k_pool.shape}, "
            f"v {v_pool.shape}")
    if heads % kvh:
        raise ValueError(f"heads {heads} not a multiple of kv_heads {kvh}")
    if k_chunk.shape != (w, kvh, hd) or v_chunk.shape != (w, kvh, hd):
        raise ValueError(
            f"chunk K/V must be (W, kv_heads, head_dim) = {(w, kvh, hd)}; "
            f"got k {k_chunk.shape}, v {v_chunk.shape}")
    if table_row.ndim != 1:
        raise ValueError(
            f"table_row must be ONE slot's table (max_table,); got "
            f"{table_row.shape}")
    group = heads // kvh
    # (W, kvh, group, hd) -> (kvh, W*group, hd); row r = w_idx*group + g
    q3 = q.reshape(w, kvh, group, hd).transpose(1, 0, 2, 3).reshape(
        kvh, w * group, hd)
    kc3 = k_chunk.transpose(1, 0, 2)  # (kvh, W, hd)
    vc3 = v_chunk.transpose(1, 0, 2)
    meta = jnp.stack([jnp.asarray(offset, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])
    out = _chunk_attention_call(q3, k_pool, v_pool, kc3, vc3,
                                table_row.astype(jnp.int32), meta,
                                interpret=interpret)
    return out.reshape(kvh, w, group, hd).transpose(1, 0, 2, 3).reshape(
        w, heads, hd)


def chunk_attention_dense(
    q: jax.Array,       # (W, heads, head_dim)
    k_lane: jax.Array,  # (max_len, kv_heads, head_dim) — ONE slot's lane
    v_lane: jax.Array,
    k_chunk: jax.Array,  # (W, kv_heads, head_dim)
    v_chunk: jax.Array,
    offset: jax.Array,
    n_valid: jax.Array,
    *,
    block_size: int = 16,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`chunk_attention` over a dense per-slot lane.

    The lane is viewed as a single-slot pool with the identity block
    table (padded up to a ``block_size`` multiple; pad rows sit at
    ``kpos >= max_len > offset`` so the prefix mask drops them), which
    lets ONE kernel body serve both serving layouts — the dense/paged
    parity matrix pins the same code path on each.
    """
    max_len, kvh, hd = k_lane.shape
    bs = max(1, min(block_size, max_len))
    pad = (-max_len) % bs
    if pad:
        k_lane = jnp.pad(k_lane, ((0, pad), (0, 0), (0, 0)))
        v_lane = jnp.pad(v_lane, ((0, pad), (0, 0), (0, 0)))
    n_table = (max_len + pad) // bs
    k_pool = k_lane.reshape(n_table, bs, kvh, hd).transpose(2, 0, 1, 3)
    v_pool = v_lane.reshape(n_table, bs, kvh, hd).transpose(2, 0, 1, 3)
    table_row = jnp.arange(n_table, dtype=jnp.int32)
    return chunk_attention(q, k_pool, v_pool, table_row, k_chunk, v_chunk,
                           offset, n_valid, interpret=interpret)


__all__ = ["chunk_attention", "chunk_attention_dense"]
