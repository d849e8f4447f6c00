"""Fused Pallas paged-attention decode kernel.

Single-query (decode-step) attention straight against the paged KV layout
of :class:`repro.nn.attention.PagedKVCache`: the shared head-major block
pool of every layer ``(n_layers, kv_heads, n_blocks, block_size,
head_dim)`` with the layer to read, the per-slot block tables, and the
per-slot positions.  The layer is a scalar-prefetch operand, so the
layer scan hands the kernel the whole pool and no layer slice is ever
cut out of it.  The dense-gather baseline first
materializes a ``(batch, max_len, kv_heads, head_dim)`` view of every
slot's cache (``pool[:, table[b, p // bs], p % bs]``) and then runs
masked attention over it — a full HBM round-trip of the
whole gathered cache per decode step.  This kernel fuses the gather into
a flash-style online-softmax loop over pool blocks copied into VMEM
straight from the table entries (the block table is a scalar-prefetch
operand), and the dense view never exists.

Walk bound: slot ``b`` walks only its first ``n_walk[b] = min(pos[b] //
bs + 1, last_mapped[b])`` table entries (:func:`walk_bound`), where
``last_mapped`` is one past the slot's last non-sentinel entry (0 for
none).  Every entry at or past the bound is masked anyway (past ``pos``,
or the sentinel), so skipping it changes nothing but float ordering: an
idle or released slot walks nothing and emits zeros, a prefilling slot
parked at ``pos = max_table * bs`` walks its reservation only, and a live
slot stops at its position.

Grid layout: ``(b over slots, kh over KV heads)``.  Each program zeroes
the online-softmax carry, then a ``fori_loop`` runs over ``ceil(n_walk[b]
/ P)`` compute blocks of ``P`` pages each (:func:`pages_per_block`, from
the block size and table width alone; the last block may reach past the
table, and its pages past the bound are neither fetched nor attended).
The pool stays in HBM; each page below the bound is one
``make_async_copy`` of block ``[layer, kh, table[b, i]]`` (clamped to
a real row — the unmapped sentinel ``n_blocks`` is masked in-kernel
instead) into one of two VMEM buffers, so block ``j + 1``'s copies are in
flight while block ``j`` accumulates ``softmax(q k^T / sqrt(d)) v`` for
the ``group = heads // kv_heads`` query heads that share KV head ``kh``.
The program then emits the normalized output block.

Masking happens in-kernel, mirroring the dense-gather semantics:
positions ``kpos > pos[b]`` (ragged per-slot lengths) and blocks whose
table entry is the sentinel (never mapped, or released after eviction)
contribute exactly zero.  A fully-masked slot (e.g. an idle decode slot
whose table was released) emits zeros via the guarded division rather
than NaN.

GQA/MQA fall out of the layout: ``q`` is reshaped to ``(batch, kv_heads,
group, head_dim)`` and each program attends one KV head's query group.

The pool is head-major so that each fetched block is ``(block_size,
head_dim)`` in the last two dims: the TPU compiler requires a block's last
two dims to be multiples of the ``(8, 128)`` tile (or the whole array
dims), which a one-head slice in the second-minor place never is.

Under tensor parallelism the kernel runs shard-local
(``repro.nn.attention``, under ``shard_map``): each call sees one shard's
KV heads and their query groups, and ``walk_bound`` takes ``n_blocks``
from that local pool.  Blocks are global on every shard, so the local
count is the global one and the table's sentinel means the same there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret
from repro.kernels.ref import NEG_INF  # one mask fill value, kernel == oracle

#: keys a compute block aims to hold; its page count follows from the
#: block size (:func:`pages_per_block`).  On one TPU v5e at qwen2.5-3b
#: decode shapes (32 slots, bs 16, bf16) 128 / 256 / 512 keys took 0.49 /
#: 0.42 / 0.38 ms a layer
_BLOCK_KEYS = 512


def pages_per_block(block_size: int, n_table: int) -> int:
    """Pages ``P`` of one compute block: enough for :data:`_BLOCK_KEYS`
    keys, at least one and at most the table width."""
    return max(1, min(n_table, _BLOCK_KEYS // block_size))


def walk_bound(table, pos, block_size: int, n_blocks: int):
    """Table entries each slot's decode query walks: ``min(pos // bs + 1,
    last_mapped)``, ``last_mapped`` one past the row's last entry that is
    not the sentinel ``n_blocks`` (0 for an all-sentinel row).  A sentinel
    hole below the last mapped entry stays inside the bound.

    The kernel wrapper calls it on device arrays; the engine's
    ``decode_walk_share`` counter on the host's numpy mirror, with no
    device read."""
    xp = jnp if isinstance(table, jax.Array) else np
    n_table = table.shape[-1]
    last_mapped = ((table != n_blocks) * xp.arange(1, n_table + 1)).max(-1)
    return xp.minimum(pos // block_size + 1, last_mapped)


def _paged_attn_kernel(walk_ref, table_ref, pos_ref, layer_ref,  # prefetch
                       q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                       block_size: int, n_blocks: int, n_table: int,
                       pages: int):
    b, kh, layer = pl.program_id(0), pl.program_id(1), layer_ref[0]
    n_walk = walk_ref[b]
    pos = pos_ref[b]
    keys = pages * block_size
    n_steps = (n_walk + pages - 1) // pages

    def copies(j, slot, p):
        # a sentinel entry is clamped to a real block for the fetch; its
        # lanes are masked below
        bid = jnp.minimum(table_ref[b, j * pages + p], n_blocks - 1)
        return [pltpu.make_async_copy(pool.at[layer, kh, bid],
                                      buf.at[slot, p],
                                      sems.at[i, slot])
                for i, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    def fetch(j, slot, wait=False):
        """Start (or wait for) compute block ``j``'s page copies into
        buffer ``slot``: one per page below the bound."""
        for p in range(pages):
            @pl.when(j * pages + p < n_walk)
            def _page():
                for c in copies(j, slot, p):
                    if wait:
                        c.wait()
                    else:
                        c.start()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0, 0].astype(jnp.float32)        # (group, head_dim)
    hd = q.shape[-1]

    @pl.when(n_steps > 0)
    def _first():
        fetch(0, 0)

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_steps)
        def _next():
            fetch(j + 1, 1 - slot)

        fetch(j, slot, wait=True)
        k = k_buf[slot].astype(jnp.float32).reshape(keys, hd)
        v = v_buf[slot].astype(jnp.float32).reshape(keys, hd)
        # rows of pages past the bound were not fetched: the buffer holds
        # what an earlier block left there, so they must not reach p @ v
        row = j * keys + jax.lax.broadcasted_iota(jnp.int32, (keys, 1), 0)
        v = jnp.where(row < n_walk * block_size, v, 0.0)
        logits = jax.lax.dot_general(           # (group, keys)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(hd))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        mapped = jnp.zeros((1, keys), jnp.int32)
        for p in range(pages):
            i = j * pages + p
            live = ((i < n_walk) & (table_ref[b, jnp.minimum(i, n_table - 1)]
                                    != n_blocks)).astype(jnp.int32)
            in_page = (lane >= p * block_size) & (lane < (p + 1) * block_size)
            mapped = jnp.where(in_page, live, mapped)
        valid = (j * keys + lane <= pos) & (mapped > 0)
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_ref[...]                     # (group, 1)
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        # the explicit where (not just the NEG_INF fill) matters: while every
        # block so far is masked, m_new == NEG_INF and exp(logits - m_new)
        # would be exp(0) == 1 on the masked lanes
        probs = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + probs.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    # guarded division: a fully-masked slot (all-sentinel table) has l == 0
    # and must emit zeros, not NaN
    o_ref[0, 0] = (acc_ref[...] /
                   jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_call(q4, k_pool, v_pool, table, pos, layer, *,
                          interpret: bool):
    batch, kvh, group, hd = q4.shape
    n_blocks, bs = k_pool.shape[2:4]
    n_table = table.shape[1]
    pages = pages_per_block(bs, n_table)
    walk = walk_bound(table, pos, bs, n_blocks).astype(jnp.int32)

    def qo_map(b, kh, *_):
        return (b, kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, kvh),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), qo_map),
            pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), qo_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, hd), k_pool.dtype),  # double buffers
            pltpu.VMEM((2, pages, bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),       # (k / v, buffer)
            pltpu.VMEM((group, 1), jnp.float32),   # running max
            pltpu.VMEM((group, 1), jnp.float32),   # running sum
            pltpu.VMEM((group, hd), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, block_size=bs,
                          n_blocks=n_blocks, n_table=n_table, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kvh, group, hd), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(walk, table, pos, layer, q4, k_pool, v_pool)


def paged_attention(
    q: jax.Array,       # (batch, heads, head_dim) — the one decode query
    k_pool: jax.Array,  # ([layers,] kv_heads, n_blocks, block_size, head_dim)
    v_pool: jax.Array,  # ([layers,] kv_heads, n_blocks, block_size, head_dim)
    table: jax.Array,   # (batch, max_table) int32; sentinel == n_blocks
    pos: jax.Array,     # (batch,) int32 — query position; attends kpos <= pos
    layer: jax.Array | int = 0,  # () int32 — the pool layer to read
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused single-query attention against layer ``layer`` of the paged
    KV pool (a pool without the layer dim is one layer).

    Returns ``(batch, heads, head_dim)`` in ``q.dtype``.  Semantics match
    :func:`repro.kernels.ref.paged_attention_ref` (same masking, fp32
    accumulation); vs the dense-gather baseline the only difference is
    online-softmax float ordering.  ``interpret=None`` auto-selects
    interpret mode off-TPU (see :func:`repro.kernels.ops.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    batch, heads, hd = q.shape
    kvh, n_blocks, bs, hd_k = k_pool.shape[1:]
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
                                pos.astype(jnp.int32),
                                jnp.asarray(layer, jnp.int32).reshape(1),
                                interpret=interpret)
    return out.reshape(batch, heads, hd)


__all__ = ["pages_per_block", "paged_attention", "walk_bound"]
