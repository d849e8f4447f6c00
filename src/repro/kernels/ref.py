"""Pure-jnp oracles for the Pallas kernels (the correctness reference)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


NEG_INF = -1e30  # must stay equal to repro.nn.attention.NEG_INF (see there)


def led_matmul_ref(x: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """y = (x @ A) @ B with fp32 accumulation.

    x: (..., K); a: (..., K, R); b: (..., R, N) -> y: (..., N) in x.dtype.
    a/b may carry leading stack axes (the shapes auto_fact emits for
    layer-scanned or expert-stacked weights); ``matmul`` broadcasting pairs
    them with x's leading axes, exactly like the ``(x @ A) @ B`` the LED
    layer computes.
    """
    t = jnp.matmul(x.astype(jnp.float32), a.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    y = jnp.matmul(t, b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        table: jax.Array, pos: jax.Array) -> jax.Array:
    """Pure-jnp oracle for :func:`repro.kernels.paged_attention`.

    Materializes the dense gather the fused kernel avoids, then runs
    masked single-query attention with the exact kernel semantics: fp32
    accumulation, ``kpos <= pos`` and sentinel-block masking, and a
    guarded division so a fully-masked slot yields zeros (``jax.nn.
    softmax`` would yield uniform weights there instead).

    q: (batch, heads, head_dim); k/v_pool: (kv_heads, n_blocks,
    block_size, head_dim); table: (batch, max_table) int32 with sentinel
    ``n_blocks``; pos: (batch,) int32 -> (batch, heads, head_dim).
    """
    batch, heads, hd = q.shape
    kvh, n_blocks, bs, _ = k_pool.shape
    group = heads // kvh
    n_table = table.shape[1]
    kpos = jnp.arange(n_table * bs)
    safe = jnp.minimum(table, n_blocks - 1)  # clamp sentinel for the gather
    blk, off = safe[:, kpos // bs], (kpos % bs)[None, :]
    gk = k_pool[:, blk, off].astype(jnp.float32)  # (kvh, batch, S, hd)
    gv = v_pool[:, blk, off].astype(jnp.float32)
    valid = ((kpos[None, :] <= pos[:, None])
             & (table[:, kpos // bs] != n_blocks))  # (batch, S)
    qf = q.astype(jnp.float32).reshape(batch, kvh, group, hd)
    logits = jnp.einsum("bkgd,kbsd->bkgs", qf, gk) / jnp.sqrt(
        jnp.float32(hd))
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :], jnp.exp(logits - m), 0.0)
    out = jnp.einsum("bkgs,kbsd->bkgd", p, gv) / jnp.maximum(
        p.sum(-1, keepdims=True), 1e-30)
    return out.reshape(batch, heads, hd).astype(q.dtype)


def chunk_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        table_row: jax.Array, k_chunk: jax.Array,
                        v_chunk: jax.Array, offset: jax.Array,
                        n_valid: jax.Array) -> jax.Array:
    """Pure-jnp oracle for :func:`repro.kernels.chunk_attention`.

    One slot's bucket-padded prompt chunk attends (a) the resident paged
    prefix — dense gather of the slot's logical lane through its block
    table, masked ``kpos < offset`` plus sentinel-block masking — and
    (b) the chunk's own fresh K/V under the in-chunk causal + padding
    mask ``(j <= r) & (j < n_valid)`` (query row ``r`` sits at absolute
    position ``offset + r``, so together the two halves reproduce the
    ``kpos <= qpos`` masking of the dense ``prefill_chunk`` gather for
    every valid row).  fp32 accumulation, guarded division.

    q: (W, heads, head_dim); k/v_pool: (kv_heads, n_blocks, block_size,
    head_dim); table_row: (max_table,) int32 with sentinel ``n_blocks``;
    k/v_chunk: (W, kv_heads, head_dim); offset/n_valid: () int32
    -> (W, heads, head_dim).
    """
    w, heads, hd = q.shape
    kvh, n_blocks, bs, _ = k_pool.shape
    group = heads // kvh
    n_table = table_row.shape[0]
    kpos = jnp.arange(n_table * bs)
    safe = jnp.minimum(table_row, n_blocks - 1)  # clamp sentinel for gather
    blk, off = safe[kpos // bs], kpos % bs
    gk = k_pool[:, blk, off].transpose(1, 0, 2).astype(jnp.float32)
    gv = v_pool[:, blk, off].transpose(1, 0, 2).astype(jnp.float32)
    prefix_valid = (kpos < offset) & (table_row[kpos // bs] != n_blocks)
    j = jnp.arange(w)
    chunk_valid = (j[None, :] <= j[:, None]) & (j[None, :] < n_valid)
    qf = q.astype(jnp.float32).reshape(w, kvh, group, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    lp = jnp.einsum("wkgd,skd->wkgs", qf, gk) * scale
    lc = jnp.einsum("wkgd,jkd->wkgj", qf,
                    k_chunk.astype(jnp.float32)) * scale
    valid = jnp.concatenate(
        [jnp.broadcast_to(prefix_valid[None, :], (w, n_table * bs)),
         chunk_valid], axis=-1)[:, None, None, :]  # (W, 1, 1, S+W)
    logits = jnp.where(valid, jnp.concatenate([lp, lc], axis=-1), NEG_INF)
    m = logits.max(-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(logits - m), 0.0)
    av = jnp.concatenate([gv, v_chunk.astype(jnp.float32)], axis=0)
    out = jnp.einsum("wkgs,skd->wkgd", p, av) / jnp.maximum(
        p.sum(-1, keepdims=True), 1e-30)
    return out.reshape(w, heads, hd).astype(q.dtype)
