"""Grouped-query attention with RoPE, sliding windows, and KV caching.

One module covers every assigned attention variant:
  * MHA (kv_heads == heads), GQA (kv_heads < heads), MQA (kv_heads == 1)
  * optional QKV bias (qwen2.5)
  * optional sliding-window mask (hymba local layers)
  * optional cross-attention (whisper decoder): keys/values from ``context``
  * KV-cache decode path (one new token against a pre-filled cache)

The projections are plain ``Linear`` modules, so Greenformer's ``auto_fact``
factorizes them into LED layers transparently.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain_acts
from repro.nn.linear import Linear
from repro.nn.module import Module, static_field
from repro.nn.rotary import apply_rope

# mask fill value — must stay equal to repro.kernels.ref.NEG_INF (the pallas
# kernels and their oracles) for the paged-decode bit-identity contract; kept
# as a local literal because nn only imports repro.kernels lazily (pallas
# must not load for training-only use)
NEG_INF = -1e30


class UnsupportedCacheError(ValueError):
    """A model/config's cache family cannot back the requested cache layout
    or serving mode.

    Lives beside the cache types so the model layer can raise it without
    depending on ``repro.serve`` (which re-exports it).  Subclasses
    ``ValueError`` for backwards compatibility with callers that caught the
    old unstructured errors.  ``roadmap_item`` names the ROADMAP entry that
    would lift the limitation."""

    def __init__(self, message: str, *, roadmap_item: Optional[str] = None):
        if roadmap_item:
            message = f"{message} [ROADMAP: {roadmap_item}]"
        super().__init__(message)
        self.roadmap_item = roadmap_item


class KVCache(NamedTuple):
    k: jax.Array  # (batch, max_len, kv_heads, head_dim)
    v: jax.Array  # (batch, max_len, kv_heads, head_dim)
    length: jax.Array  # () int32 — number of valid positions; or (batch,)
    # int32 in per-slot mode (continuous batching: each row advances
    # independently, see ``repro.serve``).

    @staticmethod
    def zeros(batch: int, max_len: int, kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16, per_slot: bool = False) -> "KVCache":
        return KVCache(
            k=jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
            v=jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
            length=jnp.zeros((batch,) if per_slot else (), jnp.int32),
        )


class PagedKVCache(NamedTuple):
    """Paged (block-table) KV layout for continuous batching.

    Instead of each slot reserving a dense ``max_len`` lane, all slots
    share one pool of fixed-size blocks; a per-slot block table maps
    logical position ``p`` to block ``table[slot, p // bs]``, row
    ``p % bs`` (flat pool row ``block * bs + p % bs``, the form the host
    side hands over).  HBM spent on KV is proportional to live tokens,
    not to ``batch * max_len``.  Block ownership, refcounts, and prefix
    sharing live host-side in :mod:`repro.serve.paging`; table entries
    equal to ``n_blocks`` (one past the last block) are the unmapped
    sentinel — scatters there drop, gathers clip into lanes the position
    mask already excludes.

    The pool is head-major so that one KV head's block is a contiguous
    ``(block_size, head_dim)`` tile: the Pallas kernels fetch exactly
    that, and the TPU compiler refuses a block whose last two dims are
    not whole ``(8, 128)`` tiles.
    """

    k: jax.Array  # (kv_heads, n_blocks, block_size, head_dim)
    v: jax.Array  # (kv_heads, n_blocks, block_size, head_dim)
    table: jax.Array  # (batch, max_blocks_per_seq) int32 pool block ids
    length: jax.Array  # (batch,) int32 — valid positions per slot

    # constructed by ``TransformerLM.init_paged_cache`` (which stacks a
    # leading n_layers dim onto k/v/length); no bare ``zeros`` here so the
    # two shape contracts cannot drift apart


class Attention(Module):
    q_proj: Linear
    k_proj: Linear
    v_proj: Linear
    o_proj: Linear
    num_heads: int = static_field(default=8)
    num_kv_heads: int = static_field(default=8)
    head_dim: int = static_field(default=64)
    rope: bool = static_field(default=True)
    rope_theta: float = static_field(default=10000.0)
    window: int = static_field(default=0)  # 0 = global; >0 = sliding window
    causal: bool = static_field(default=True)
    chunk: int = static_field(default=0)  # >0: flash-style blockwise attention

    @staticmethod
    def create(key, dim: int, num_heads: int, num_kv_heads: int, *,
               head_dim: Optional[int] = None, qkv_bias: bool = False,
               rope: bool = True, rope_theta: float = 10000.0, window: int = 0,
               causal: bool = True, chunk: int = 0,
               dtype=jnp.float32) -> "Attention":
        head_dim = head_dim or dim // num_heads
        kq, kk, kv, ko = jax.random.split(key, 4)
        return Attention(
            q_proj=Linear.create(kq, dim, num_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
            k_proj=Linear.create(kk, dim, num_kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
            v_proj=Linear.create(kv, dim, num_kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
            o_proj=Linear.create(ko, num_heads * head_dim, dim, use_bias=False, dtype=dtype),
            num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
            rope=rope, rope_theta=rope_theta, window=window, causal=causal,
            chunk=chunk,
        )

    # -- helpers ------------------------------------------------------------

    def _qkv(self, x, context=None, positions=None, kv_positions=None):
        b, s, _ = x.shape
        ctx = x if context is None else context
        q = self.q_proj(x).reshape(b, s, self.num_heads, self.head_dim)
        k = self.k_proj(ctx).reshape(b, ctx.shape[1], self.num_kv_heads, self.head_dim)
        v = self.v_proj(ctx).reshape(b, ctx.shape[1], self.num_kv_heads, self.head_dim)
        if self.rope:
            if positions is None:
                positions = jnp.arange(s)[None, :]
            if kv_positions is None:
                kv_positions = jnp.arange(ctx.shape[1])[None, :]
            q = apply_rope(q, positions, theta=self.rope_theta)
            k = apply_rope(k, kv_positions, theta=self.rope_theta)
        return q, k, v

    def _attend(self, q, k, v, mask):
        """q: (b, sq, h, d); k/v: (b, sk, kvh, d); mask: (b, 1, sq, sk) bool."""
        group = self.num_heads // self.num_kv_heads
        b, sq, h, d = q.shape
        sk = k.shape[1]
        q = q.reshape(b, sq, self.num_kv_heads, group, d)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
        logits = logits / jnp.sqrt(d).astype(jnp.float32)
        if mask is not None:
            logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        return out.reshape(b, sq, h * d)

    def _attend_chunked(self, q, k, v):
        """Flash-style blockwise attention: O(chunk²) temporaries instead of
        O(S²).  Online-softmax accumulation over KV blocks, lax.map over Q
        blocks.  Respects causal + sliding-window masks via block position
        offsets.  Self-attention full-sequence path only (training/prefill)."""
        c = self.chunk
        b, sq, h, d = q.shape
        sk = k.shape[1]
        pad_q, pad_k = (-sq) % c, (-sk) % c
        qpad = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        kpad = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vpad = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        nq, nk = (sq + pad_q) // c, (sk + pad_k) // c
        group = self.num_heads // self.num_kv_heads
        kvh = self.num_kv_heads
        qb = qpad.reshape(b, nq, c, kvh, group, d).astype(jnp.float32)
        kb = kpad.reshape(b, nk, c, kvh, d).astype(jnp.float32)
        vb = vpad.reshape(b, nk, c, kvh, d).astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(d)
        kpos_in = jnp.arange(c)
        qpos_in = jnp.arange(c)

        def q_block(qi):
            qblk = qb[:, qi]  # (b, c, kvh, g, d)

            def kv_step(carry, ki):
                m, l, acc = carry
                kblk, vblk = kb[:, ki], vb[:, ki]
                logits = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk) * scale
                qpos = qi * c + qpos_in
                kpos = ki * c + kpos_in
                valid = kpos[None, :] < sk
                if self.causal:
                    valid = valid & (kpos[None, :] <= qpos[:, None])
                if self.window > 0:
                    valid = valid & (kpos[None, :] > qpos[:, None] - self.window)
                logits = jnp.where(valid[None, None, None, :, :], logits,
                                   NEG_INF)
                m_new = jnp.maximum(m, logits.max(-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(-1)
                acc_new = (acc * corr[..., None]
                           + jnp.einsum("bkgqs,bskd->bkgqd", p, vblk))
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((b, kvh, group, c), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, kvh, group, c), jnp.float32)
            a0 = jnp.zeros((b, kvh, group, c, d), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nk))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            # (b, kvh, g, c, d) -> (b, c, kvh*g*d)
            return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h * d)

        blocks = jax.lax.map(q_block, jnp.arange(nq))  # (nq, b, c, h*d)
        out = blocks.transpose(1, 0, 2, 3).reshape(b, nq * c, h * d)
        return out[:, :sq].astype(q.dtype)

    def _causal_mask(self, sq, sk, q_offset=0):
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None] if self.causal else jnp.ones((sq, sk), bool)
        if self.window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - self.window)
        return mask[None, None, :, :]  # (1, 1, sq, sk) -> broadcasts over (b, kvh)

    # -- forward paths ------------------------------------------------------

    def __call__(self, x: jax.Array, *, context: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        """Full-sequence forward (training / prefill without cache)."""
        q, k, v = self._qkv(x, context=context, positions=positions)
        if context is None and self.chunk > 0 and x.shape[1] > self.chunk:
            out = self._attend_chunked(q, k, v)
            return self.o_proj(out)
        if context is None:
            mask = self._causal_mask(x.shape[1], x.shape[1])
        else:
            mask = None  # cross-attention: attend to the whole context
        out = self._attend(q, k, v, mask)
        return self.o_proj(out)

    def project_kv(self, context: jax.Array):
        """Precompute cross-attention K/V from an encoder context."""
        b, t, _ = context.shape
        k = self.k_proj(context).reshape(b, t, self.num_kv_heads, self.head_dim)
        v = self.v_proj(context).reshape(b, t, self.num_kv_heads, self.head_dim)
        return k, v

    def attend_kv(self, x: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        """Cross-attend ``x`` against precomputed K/V (no mask, no rope)."""
        b, s, _ = x.shape
        q = self.q_proj(x).reshape(b, s, self.num_heads, self.head_dim)
        return self.o_proj(self._attend(q, k, v, None))

    def _is_ring(self, cache: KVCache) -> bool:
        """Ring-buffer mode: a sliding-window layer whose cache holds exactly
        ``window`` slots — slot(p) = p % window.  O(window) decode memory
        regardless of context length (the long_500k path)."""
        return self.window > 0 and cache.k.shape[1] == self.window

    def prefill(self, x: jax.Array, cache: KVCache) -> tuple[jax.Array, KVCache]:
        """Process a prompt, fill the cache, return outputs + updated cache."""
        b, s, _ = x.shape
        q, k, v = self._qkv(x)
        if self.chunk > 0 and s > self.chunk:
            out = self._attend_chunked(q, k, v)
        else:
            out = self._attend(q, k, v, self._causal_mask(s, s))
        k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
        if self._is_ring(cache):
            w = self.window
            keep = min(s, w)
            slots = (jnp.arange(s - keep, s)) % w
            new_k = cache.k.at[:, slots].set(k[:, s - keep:])
            new_v = cache.v.at[:, slots].set(v[:, s - keep:])
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k, 0, axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v, 0, axis=1)
        return (constrain_acts(self.o_proj(out)),
                KVCache(new_k, new_v, jnp.asarray(s, jnp.int32)))

    def prefill_chunk(self, x: jax.Array, cache, *, slot: jax.Array,
                      offset: jax.Array, n_valid: jax.Array,
                      dst: Optional[jax.Array] = None,
                      prefill_kernel: str = "reference"):
        """Consume one prompt chunk for ONE slot of a batched serving cache.

        ``x``: (1, W, dim) — a bucket-padded span of the slot's prompt whose
        first ``n_valid`` rows are real tokens starting at absolute position
        ``offset`` (RoPE positions, causal mask, and cache writes are all
        offset-relative, so a prompt can be fed in any chunking and produce
        the same K/V rows and the same last-token logits as one monolithic
        prefill).  The chunk attends against everything already resident in
        the slot's lane — earlier chunks of this prompt AND, for the paged
        layout, shared prefix blocks written by an earlier request — which
        is what lets prefix-aware admission *start* after the cached prefix
        instead of recomputing it.

        Dense per-slot :class:`KVCache`: chunk K/V rows are scattered
        straight into the slot's lane at ``offset + i`` (padding rows are
        routed out of range and dropped), and attention gathers the full
        lane under a ``kpos <= qpos`` mask.

        Ring-buffer :class:`KVCache` (sliding-window layer whose lane
        holds exactly ``window`` slots): the chunk attends against the
        concatenation of the slot's resident ring lanes and its own fresh
        K/V — ring lane ``i`` holds absolute position ``offset - 1 -
        ((offset - 1 - i) mod window)`` (the newest position below
        ``offset`` on that lane; negative means this request never wrote
        it, which also masks out stale lanes from a recycled slot without
        any reset), and both halves carry offset-relative causal +
        sliding-window masks, so any chunking is wraparound-safe.  Only
        the newest ``min(n_valid, window)`` chunk rows are scattered back
        (``slot(p) = p % window``); older rows of an over-wide chunk and
        padding rows route to the out-of-range lane and drop.

        :class:`PagedKVCache`: ``dst`` gives the flat pool row for each of
        the W chunk positions — the engine points padding AND cached-prefix
        positions at the out-of-range sentinel row, so ``mode='drop'``
        leaves shared blocks untouched (a prefix hit is never rewritten,
        even with identical bytes) — and attention gathers the slot's
        logical lane through its block table.

        ``prefill_kernel`` selects the chunk attention implementation for
        the paged and dense layouts: ``"reference"`` is the dense gather +
        masked softmax above; ``"pallas"`` is the flash-style
        :func:`repro.kernels.chunk_attention` kernel — prefix blocks
        stream through VMEM inside an online-softmax loop and the
        gathered lane view is never materialized.  Valid rows match the
        reference to float tolerance (padding rows carry no contract —
        the engine never reads them); ring-buffer lanes refuse the
        kernel (their wraparound gather has no paged-pool analogue).

        Returns ``(chunk outputs (1, W, dim), updated cache)`` with the
        slot's length advanced to ``offset + n_valid``.
        """
        if prefill_kernel not in ("reference", "pallas"):
            raise ValueError(f"unknown prefill_kernel {prefill_kernel!r}")
        w = x.shape[1]
        qpos = offset + jnp.arange(w)  # (W,) absolute positions
        q, k, v = self._qkv(x, positions=qpos[None, :],
                            kv_positions=qpos[None, :])
        if isinstance(cache, PagedKVCache):
            if self.window > 0:
                raise NotImplementedError(
                    "paged chunked prefill supports global attention only; "
                    "sliding-window layers use the ring-buffer KVCache path")
            bs = cache.k.shape[2]
            max_table = cache.table.shape[1]
            # flat rows -> (block, row); the sentinel row n_blocks * bs
            # lands on block n_blocks, out of range, and drops
            blk, off = dst // bs, dst % bs
            pool_k = cache.k.at[:, blk, off].set(
                k[0].swapaxes(0, 1).astype(cache.k.dtype), mode="drop")
            pool_v = cache.v.at[:, blk, off].set(
                v[0].swapaxes(0, 1).astype(cache.v.dtype), mode="drop")
            if prefill_kernel == "pallas":
                from repro.kernels.chunk_attention import chunk_attention

                out = chunk_attention(
                    q[0], pool_k, pool_v, cache.table[slot],
                    k[0].astype(pool_k.dtype), v[0].astype(pool_v.dtype),
                    offset, n_valid).reshape(1, w, -1)
            else:
                kpos = jnp.arange(max_table * bs)
                blk_s, off_s = cache.table[slot, kpos // bs], kpos % bs
                # (kvh, S, hd) -> (1, S, kvh, hd)
                gk = pool_k[:, blk_s, off_s].swapaxes(0, 1)[None]
                gv = pool_v[:, blk_s, off_s].swapaxes(0, 1)[None]
                valid = kpos[None, :] <= qpos[:, None]  # (W, S)
                out = self._attend(q, gk.astype(x.dtype), gv.astype(x.dtype),
                                   valid[None, None])
            length = cache.length.at[slot].set(offset + n_valid)
            new_cache = PagedKVCache(pool_k, pool_v, cache.table, length)
        elif self._is_ring(cache):
            if prefill_kernel == "pallas":
                raise NotImplementedError(
                    "prefill_kernel='pallas' streams a position-addressable "
                    "KV prefix (paged pool or dense lane); ring-buffer "
                    "(sliding-window) lanes wrap around and use the "
                    "reference path")
            ring = self.window
            i = jnp.arange(ring)
            # lane i holds the newest absolute position < offset congruent
            # to i mod ring; negative => never written by THIS request
            # (covers both a cold lane and stale bytes left by the slot's
            # previous occupant — no reset pass needed)
            p_lane = (offset - 1) - jnp.mod((offset - 1) - i, ring)
            ring_k = cache.k[slot][None].astype(x.dtype)  # (1, ring, kvh, hd)
            ring_v = cache.v[slot][None].astype(x.dtype)
            ring_valid = ((p_lane[None, :] >= 0)
                          & (p_lane[None, :] > qpos[:, None] - ring))
            j = jnp.arange(w)
            self_valid = ((j[None, :] <= j[:, None])         # causal in-chunk
                          & (j[None, :] < n_valid)           # padding
                          & (qpos[None, :] > qpos[:, None] - ring))
            mask = jnp.concatenate([ring_valid, self_valid], axis=1)
            gk = jnp.concatenate([ring_k, k.astype(x.dtype)], axis=1)
            gv = jnp.concatenate([ring_v, v.astype(x.dtype)], axis=1)
            out = self._attend(q, gk, gv, mask[None, None])
            # scatter the newest min(n_valid, ring) rows to slot(p) = p %
            # ring; rows a wider-than-window chunk already superseded and
            # padding rows route to the out-of-range lane and drop (the
            # survivors hit pairwise-distinct lanes: ring consecutive
            # positions)
            live = (j < n_valid) & (j >= n_valid - ring)
            lanes = jnp.where(live, (offset + j) % ring, ring)
            new_k = cache.k.at[slot, lanes].set(k[0].astype(cache.k.dtype),
                                                mode="drop")
            new_v = cache.v.at[slot, lanes].set(v[0].astype(cache.v.dtype),
                                                mode="drop")
            length = cache.length.at[slot].set(offset + n_valid)
            new_cache = KVCache(new_k, new_v, length)
        else:
            max_len = cache.k.shape[1]
            wpos = jnp.where(jnp.arange(w) < n_valid, qpos, max_len)
            new_k = cache.k.at[slot, wpos].set(k[0].astype(cache.k.dtype),
                                               mode="drop")
            new_v = cache.v.at[slot, wpos].set(v[0].astype(cache.v.dtype),
                                               mode="drop")
            if prefill_kernel == "pallas":
                from repro.kernels.chunk_attention import (
                    chunk_attention_dense)

                out = chunk_attention_dense(
                    q[0], new_k[slot], new_v[slot],
                    k[0].astype(cache.k.dtype), v[0].astype(cache.v.dtype),
                    offset, n_valid).reshape(1, w, -1)
            else:
                kpos = jnp.arange(max_len)
                valid = kpos[None, :] <= qpos[:, None]  # (W, max_len)
                out = self._attend(q, new_k[slot][None].astype(x.dtype),
                                   new_v[slot][None].astype(x.dtype),
                                   valid[None, None])
            length = cache.length.at[slot].set(offset + n_valid)
            new_cache = KVCache(new_k, new_v, length)
        return constrain_acts(self.o_proj(out)), new_cache

    def decode(self, x: jax.Array, cache, *,
               decode_kernel: str = "reference") -> tuple[jax.Array, "KVCache"]:
        """Decode step for ``s`` new tokens per row. x: (batch, s, dim).

        ``s == 1`` is the ordinary autoregressive step.  ``s > 1`` is the
        multi-token step speculative verification uses: all ``s`` K/V rows
        are written first, then every query attends under a ``kpos <=
        qpos`` mask, so token ``j`` sees exactly the rows a sequential
        ``s``-step decode would have seen (intra-chunk causality) and the
        logits match the sequential ones bit-for-bit given the same cache
        contents.  Rows past the accepted prefix are overwritten by the
        next step before any query can attend them (length only advances
        by the accepted count).

        With a :class:`KVCache`, ``cache.length`` is either a scalar
        (lock-step batch: every row sits at the same position) or a
        ``(batch,)`` vector (per-slot mode for continuous batching: each row
        advances independently, with its own RoPE position, cache write
        offset, and validity mask).  With a :class:`PagedKVCache`, K/V rows
        are scattered to / gathered from the shared block pool through each
        slot's block table; ``decode_kernel`` selects the paged attention
        implementation (``"reference"`` = dense gather + masked softmax,
        ``"pallas"`` = the fused block-streaming kernel, single-token steps
        only — multi-token steps fall back to the reference gather) and is
        ignored for dense caches."""
        if isinstance(cache, PagedKVCache):
            return self._decode_paged(x, cache, kernel=decode_kernel)
        b, s, _ = x.shape
        pos = cache.length
        per_slot = pos.ndim == 1
        if self._is_ring(cache):
            if s != 1:
                raise NotImplementedError(
                    "multi-token decode targets the kv/paged layouts; "
                    "ring-buffer (sliding-window) caches decode one token "
                    "at a time")
            positions = (pos[:, None].astype(jnp.int32) if per_slot
                         else jnp.full((b, 1), pos, dtype=jnp.int32))
            q, k, v = self._qkv(x, positions=positions, kv_positions=positions)
            k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            w = self.window
            slot = pos % w
            i = jnp.arange(w)
            if per_slot:
                rows = jnp.arange(b)
                new_k = cache.k.at[rows, slot].set(k[:, 0])
                new_v = cache.v.at[rows, slot].set(v[:, 0])
                kpos = pos[:, None] - jnp.mod(pos[:, None] - i[None, :], w)
                valid = kpos >= 0  # (b, w)
                mask = valid[:, None, None, :]
            else:
                new_k = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
                new_v = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
                # slot i holds absolute position pos - ((pos - i) mod w); valid
                # once non-negative.  Window recency holds by construction.
                kpos = pos - jnp.mod(pos - i, w)
                valid = kpos >= 0
                mask = valid[None, None, None, :]
            out = self._attend(q, new_k.astype(x.dtype),
                               new_v.astype(x.dtype), mask)
            return (constrain_acts(self.o_proj(out)),
                    KVCache(new_k, new_v, pos + 1))
        kpos = jnp.arange(cache.k.shape[1])
        if per_slot:
            qpos = pos[:, None] + jnp.arange(s)[None, :]  # (b, s)
            q, k, v = self._qkv(x, positions=qpos.astype(jnp.int32),
                                kv_positions=qpos.astype(jnp.int32))
            k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            # mode='drop': a row parked at pos == max_len (slot frozen by
            # cache_full eviction, or mid-chunked-prefill with its write
            # frontier owned by prefill_chunk) must write NOWHERE — the
            # default clip would smear stale K/V into the last lane row
            rows = jnp.arange(b)
            new_k = cache.k.at[rows[:, None], qpos].set(k, mode="drop")
            new_v = cache.v.at[rows[:, None], qpos].set(v, mode="drop")
            valid = kpos[None, None, :] <= qpos[:, :, None]  # (b, s, S)
            if self.window > 0:
                valid = valid & (kpos[None, None, :]
                                 > qpos[:, :, None] - self.window)
            mask = valid[:, None]  # (b, 1, s, S)
        else:
            qpos = pos + jnp.arange(s)  # (s,)
            positions = jnp.broadcast_to(qpos[None, :], (b, s)).astype(jnp.int32)
            q, k, v = self._qkv(x, positions=positions, kv_positions=positions)
            k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            new_k = jax.lax.dynamic_update_slice(cache.k, k, (0, pos, 0, 0))
            new_v = jax.lax.dynamic_update_slice(cache.v, v, (0, pos, 0, 0))
            valid = kpos[None, :] <= qpos[:, None]  # (s, S)
            if self.window > 0:
                valid = valid & (kpos[None, :] > qpos[:, None] - self.window)
            mask = valid[None, None]  # (1, 1, s, S)
        out = self._attend(q, new_k.astype(x.dtype), new_v.astype(x.dtype), mask)
        return constrain_acts(self.o_proj(out)), KVCache(new_k, new_v, pos + s)

    def _decode_paged(self, x: jax.Array, cache: PagedKVCache,
                      kernel: str = "reference"
                      ) -> tuple[jax.Array, PagedKVCache]:
        """Decode ``s`` tokens per slot against the shared block pool.

        Each new K/V row is scattered to block ``table[b, p // bs]``, row
        ``p % bs`` for ``p = pos .. pos + s - 1`` (``mode='drop'``: slots
        whose table entry is the unmapped sentinel — finished, never
        admitted, or positions past the slot's block reservation — write
        nowhere, so a frozen slot can never clobber a block recycled to
        another request).  ``kernel="reference"`` (the dense-gather
        baseline) then gathers every mapped pool row back into logical
        order and masks ``kpos > qpos`` per query; gathers through
        sentinel entries clip into masked lanes, and exactly-NEG_INF
        masking makes their contribution a hard zero, keeping outputs
        bit-identical to the dense per-slot layout.  ``kernel="pallas"``
        replaces the gather + attention with the fused
        :func:`repro.kernels.paged_attention` kernel — blocks stream
        through VMEM inside a flash-style online-softmax loop and the
        dense ``(batch, max_len, kvh, hd)`` view is never materialized
        (sentinel and ``kpos > pos`` masking move in-kernel).  The kernel
        is single-query; multi-token steps (``s > 1``, the speculative
        verify pass) fall back to the reference gather."""
        if self.window > 0:
            raise NotImplementedError(
                "paged decode supports global attention only; sliding-window "
                "layers use the ring-buffer KVCache path")
        if kernel not in ("reference", "pallas"):
            raise ValueError(f"unknown paged decode kernel {kernel!r}")
        b, s, _ = x.shape
        pos = cache.length  # (b,)
        qpos = pos[:, None] + jnp.arange(s)[None, :]  # (b, s)
        positions = qpos.astype(jnp.int32)
        q, k, v = self._qkv(x, positions=positions, kv_positions=positions)
        nb, bs = cache.k.shape[1:3]
        max_table = cache.table.shape[1]
        # a slot frozen at pos == max_table*bs (cache_full eviction) would
        # index one past the table; clamp the lookup and route its write to
        # the sentinel block explicitly — take_along_axis's out-of-bounds
        # fill (INT32_MIN) would otherwise index from the end of the pool
        blk = jnp.take_along_axis(
            cache.table, jnp.minimum(qpos // bs, max_table - 1), axis=1)
        blk = jnp.where(qpos < max_table * bs, blk, nb)  # (b, s)
        # (b, s, kvh, hd) -> (kvh, b, s, hd), the order pool[:, blk, off]
        # indexes in
        new_k = cache.k.at[:, blk, qpos % bs].set(
            jnp.moveaxis(k, 2, 0).astype(cache.k.dtype), mode="drop")
        new_v = cache.v.at[:, blk, qpos % bs].set(
            jnp.moveaxis(v, 2, 0).astype(cache.v.dtype), mode="drop")
        if kernel == "pallas" and s == 1:
            from repro.kernels.paged_attention import paged_attention

            out = paged_attention(q[:, 0], new_k, new_v, cache.table, pos)
            out = out.reshape(b, 1, self.num_heads * self.head_dim)
        else:
            kpos = jnp.arange(max_table * bs)
            blk_s, off_s = cache.table[:, kpos // bs], (kpos % bs)[None, :]
            # (kvh, b, S, hd) -> (b, S, kvh, hd)
            gk = jnp.moveaxis(new_k[:, blk_s, off_s], 0, 2).astype(x.dtype)
            gv = jnp.moveaxis(new_v[:, blk_s, off_s], 0, 2).astype(x.dtype)
            valid = kpos[None, None, :] <= qpos[:, :, None]  # (b, s, S)
            out = self._attend(q, gk, gv, valid[:, None])
        return (constrain_acts(self.o_proj(out)),
                PagedKVCache(new_k, new_v, cache.table, pos + s))
