"""Fused Pallas paged-attention decode kernel.

Single-query (decode-step) attention straight against the paged KV layout
of :class:`repro.nn.attention.PagedKVCache`: the shared head-major block
pool ``(kv_heads, n_blocks, block_size, head_dim)``, the per-slot block
tables, and the per-slot positions.  The dense-gather baseline first
materializes a ``(batch, max_len, kv_heads, head_dim)`` view of every
slot's cache (``pool[:, table[b, p // bs], p % bs]``) and then runs
masked attention over it — a full HBM round-trip of the
whole gathered cache per decode step.  This kernel fuses the gather into
a flash-style online-softmax loop: KV blocks stream from the pool into
VMEM one at a time (the block table is a scalar-prefetch operand, so each
grid step's DMA source is ``table[b, i]`` directly) and the dense view
never exists.

Grid layout: ``(b over slots, kh over KV heads, i over table entries)``,
all sequential ("arbitrary") so the per-(b, kh) running max / sum /
accumulator scratch persists across the ``i`` steps:

  * ``i == 0``: zero the online-softmax carry.
  * every ``i``: fetch pool block ``table[b, i]`` of KV head ``kh``
    (clamped to a real row — the unmapped sentinel ``n_blocks`` is masked
    in-kernel instead),
    accumulate ``softmax(q k^T / sqrt(d)) v`` for the ``group =
    heads // kv_heads`` query heads that share KV head ``kh``.
  * ``i == last``: emit the normalized output block.

Masking happens in-kernel, mirroring the dense-gather semantics:
positions ``kpos > pos[b]`` (ragged per-slot lengths) and blocks whose
table entry is the sentinel (never mapped, or released after eviction)
contribute exactly zero.  A fully-masked slot (e.g. an idle decode slot
whose table was released) emits zeros via the guarded division rather
than NaN.

GQA/MQA fall out of the layout: ``q`` is reshaped to ``(batch, kv_heads,
group, head_dim)`` and each grid step attends one KV head's query group.

The pool is head-major so that each fetched block is ``(block_size,
head_dim)`` in the last two dims: the TPU compiler requires a block's last
two dims to be multiples of the ``(8, 128)`` tile (or the whole array
dims), which a one-head slice in the second-minor place never is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret
from repro.kernels.ref import NEG_INF  # one mask fill value, kernel == oracle


def _paged_attn_kernel(table_ref, pos_ref,  # scalar prefetch
                       q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *,
                       block_size: int, n_blocks: int, n_table: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # (group, head_dim)
    k = k_ref[0, 0].astype(jnp.float32)        # (block_size, head_dim)
    v = v_ref[0, 0].astype(jnp.float32)
    logits = jax.lax.dot_general(               # (group, block_size)
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
    pos = pos_ref[b]
    bid = table_ref[b, i]
    kpos = i * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = (kpos <= pos) & (bid != n_blocks)
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_ref[...]                         # (group, 1)
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    # the explicit where (not just the NEG_INF fill) matters: while every
    # block so far is masked, m_new == NEG_INF and exp(logits - m_new)
    # would be exp(0) == 1 on the masked lanes
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == n_table - 1)
    def _emit():
        # guarded division: a fully-masked slot (all-sentinel table) has
        # l == 0 and must emit zeros, not NaN
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_call(q4, k_pool, v_pool, table, pos, *,
                          interpret: bool):
    batch, kvh, group, hd = q4.shape
    n_blocks, bs = k_pool.shape[1:3]
    n_table = table.shape[1]

    def kv_map(b, kh, i, table_ref, pos_ref):
        # sentinel entries (n_blocks, one past the pool) are clamped to a
        # real block for the fetch; the kernel masks their lanes to zero
        return (kh, jnp.minimum(table_ref[b, i], n_blocks - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, kvh, n_table),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd),
                         lambda b, kh, i, t, p: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, kh, i, t, p: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),   # running max
            pltpu.VMEM((group, 1), jnp.float32),   # running sum
            pltpu.VMEM((group, hd), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, block_size=bs,
                          n_blocks=n_blocks, n_table=n_table),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kvh, group, hd), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(table, pos, q4, k_pool, v_pool)


def paged_attention(
    q: jax.Array,       # (batch, heads, head_dim) — the one decode query
    k_pool: jax.Array,  # (kv_heads, n_blocks, block_size, head_dim)
    v_pool: jax.Array,  # (kv_heads, n_blocks, block_size, head_dim)
    table: jax.Array,   # (batch, max_table) int32; sentinel == n_blocks
    pos: jax.Array,     # (batch,) int32 — query position; attends kpos <= pos
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused single-query attention against the paged KV pool.

    Returns ``(batch, heads, head_dim)`` in ``q.dtype``.  Semantics match
    :func:`repro.kernels.ref.paged_attention_ref` (same masking, fp32
    accumulation); vs the dense-gather baseline the only difference is
    online-softmax float ordering.  ``interpret=None`` auto-selects
    interpret mode off-TPU (see :func:`repro.kernels.ops.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    batch, heads, hd = q.shape
    kvh, n_blocks, bs, hd_k = k_pool.shape
    if hd_k != hd or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool/query shape mismatch: q {q.shape}, k {k_pool.shape}, "
            f"v {v_pool.shape}")
    if heads % kvh:
        raise ValueError(f"heads {heads} not a multiple of kv_heads {kvh}")
    if table.shape[0] != batch or pos.shape != (batch,):
        raise ValueError(
            f"table {table.shape} / pos {pos.shape} do not match batch "
            f"{batch}")
    q4 = q.reshape(batch, kvh, heads // kvh, hd)
    out = _paged_attention_call(q4, k_pool, v_pool,
                                table.astype(jnp.int32),
                                pos.astype(jnp.int32), interpret=interpret)
    return out.reshape(batch, heads, hd)


__all__ = ["paged_attention"]
