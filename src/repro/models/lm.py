"""Decoder-only transformer LM covering the dense / GQA / MoE / VLM archs.

Layers are weight-stacked and executed with ``jax.lax.scan`` so the HLO is
O(1) in depth (critical for 88-layer granite at compile time) and activation
rematerialization applies per-layer.  The VLM arch (chameleon) is early
fusion: VQ image tokens are ordinary vocabulary ids, so the backbone is this
same class (frontend stubbed per the assignment).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain_acts
from repro.nn.attention import (Attention, KVCache, PagedKVCache,
                                UnsupportedCacheError)
from repro.nn.embedding import Embedding
from repro.nn.linear import Linear
from repro.nn.mlp import SwiGLU
from repro.nn.moe import MoE
from repro.nn.module import Module, static_field
from repro.nn.norm import RMSNorm


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


class DenseBlock(Module):
    attn_norm: RMSNorm
    attn: Attention
    mlp_norm: RMSNorm
    mlp: SwiGLU

    @staticmethod
    def create(key, cfg: ArchConfig) -> "DenseBlock":
        ka, km = jax.random.split(key)
        dt = _dtype(cfg)
        return DenseBlock(
            attn_norm=RMSNorm.create(cfg.d_model, dtype=dt),
            attn=Attention.create(
                ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
                rope_theta=cfg.rope_theta, window=cfg.window,
                chunk=cfg.attn_chunk, dtype=dt),
            mlp_norm=RMSNorm.create(cfg.d_model, dtype=dt),
            mlp=SwiGLU.create(km, cfg.d_model, cfg.d_ff, dtype=dt),
        )

    def __call__(self, x):
        x = x + self.attn(self.attn_norm(x))
        x = x + self.mlp(self.mlp_norm(x))
        return x, jnp.zeros((), jnp.float32)

    def prefill(self, x, cache: KVCache):
        a, cache = self.attn.prefill(self.attn_norm(x), cache)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x))
        return x, cache

    def decode(self, x, cache: KVCache, decode_kernel: str = "reference",
               layer=0):
        a, cache = self.attn.decode(self.attn_norm(x), cache,
                                    decode_kernel=decode_kernel, layer=layer)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x))
        return x, cache

    def prefill_chunk(self, x, cache, **kw):
        a, cache = self.attn.prefill_chunk(self.attn_norm(x), cache, **kw)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x))
        return x, cache


class MoEBlock(Module):
    attn_norm: RMSNorm
    attn: Attention
    mlp_norm: RMSNorm
    mlp: MoE

    @staticmethod
    def create(key, cfg: ArchConfig) -> "MoEBlock":
        ka, km = jax.random.split(key)
        dt = _dtype(cfg)
        return MoEBlock(
            attn_norm=RMSNorm.create(cfg.d_model, dtype=dt),
            attn=Attention.create(
                ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
                rope_theta=cfg.rope_theta, chunk=cfg.attn_chunk, dtype=dt),
            mlp_norm=RMSNorm.create(cfg.d_model, dtype=dt),
            mlp=MoE.create(km, cfg.d_model, cfg.d_ff, cfg.n_experts,
                           cfg.top_k, n_shared=cfg.n_shared,
                           capacity_factor=cfg.capacity_factor, dtype=dt),
        )

    def __call__(self, x):
        x = x + self.attn(self.attn_norm(x))
        out = self.mlp(self.mlp_norm(x))
        return x + out.y, out.aux_loss

    def prefill(self, x, cache: KVCache):
        a, cache = self.attn.prefill(self.attn_norm(x), cache)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x)).y
        return x, cache

    def decode(self, x, cache: KVCache, decode_kernel: str = "reference",
               layer=0):
        a, cache = self.attn.decode(self.attn_norm(x), cache,
                                    decode_kernel=decode_kernel, layer=layer)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x)).y
        return x, cache

    def prefill_chunk(self, x, cache, **kw):
        # capacity-factor routing sees one chunk of tokens at a time here,
        # so expert-capacity dropping can differ from a monolithic prefill
        # of the same prompt; exact-capacity configs are unaffected
        a, cache = self.attn.prefill_chunk(self.attn_norm(x), cache, **kw)
        x = x + a
        x = x + self.mlp(self.mlp_norm(x)).y
        return x, cache


class TransformerLM(Module):
    embed: Embedding
    blocks: Module  # layer-stacked DenseBlock | MoEBlock
    final_norm: RMSNorm
    lm_head: Optional[Linear]  # None => tied embeddings
    n_layers: int = static_field(default=1)
    remat: bool = static_field(default=False)

    @staticmethod
    def create(key, cfg: ArchConfig, *, remat: bool = False) -> "TransformerLM":
        ke, kb, kh = jax.random.split(key, 3)
        dt = _dtype(cfg)
        block_cls = MoEBlock if cfg.n_experts else DenseBlock
        layer_keys = jax.random.split(kb, cfg.n_layers)
        blocks = jax.vmap(lambda k: block_cls.create(k, cfg))(layer_keys)
        lm_head = (None if cfg.tie_embeddings else
                   Linear.create(kh, cfg.d_model, cfg.vocab, dtype=dt))
        return TransformerLM(
            embed=Embedding.create(ke, cfg.vocab, cfg.d_model, dtype=dt),
            blocks=blocks,
            final_norm=RMSNorm.create(cfg.d_model, dtype=dt),
            lm_head=lm_head,
            n_layers=cfg.n_layers,
            remat=remat,
        )

    # -- forward --------------------------------------------------------------

    def _head(self, x):
        return self.embed.attend(x) if self.lm_head is None else self.lm_head(x)

    def __call__(self, tokens: jax.Array):
        """tokens: (batch, seq) -> logits (batch, seq, vocab), aux loss."""
        x = constrain_acts(self.embed(tokens))

        def body(carry, blk):
            x, aux = carry
            fn = (lambda b, xx: b(xx))
            if self.remat:
                fn = jax.checkpoint(fn)
            y, a = fn(blk, x)
            return (constrain_acts(y), aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   self.blocks)
        return self._head(self.final_norm(x)), aux / self.n_layers

    # -- serving --------------------------------------------------------------

    def cache_kind(self, cfg: ArchConfig) -> str:
        """Capability probe for ``repro.serve.ContinuousEngine``: which
        per-slot state family this model serves with.  Global-attention
        configs are ``"kv"`` (paged or dense per-slot lanes); sliding-
        window configs are ``"ring"`` (per-slot ring lanes — O(window)
        decode memory, cannot be paged or prefix-cached)."""
        return "ring" if cfg.window else "kv"

    def init_cache(self, batch: int, max_len: int, cfg: ArchConfig,
                   dtype=jnp.bfloat16, per_slot: bool = False) -> KVCache:
        """``per_slot=True`` gives each batch row its own length counter
        (shape ``(n_layers, batch)``) so rows decode at independent
        positions — the continuous-batching cache layout."""
        w = cfg.window
        slots = min(max_len, w) if w else max_len
        kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        lshape = (self.n_layers, batch) if per_slot else (self.n_layers,)
        return KVCache(
            k=jnp.zeros((self.n_layers, batch, slots, kvh, hd), dtype),
            v=jnp.zeros((self.n_layers, batch, slots, kvh, hd), dtype),
            length=jnp.zeros(lshape, jnp.int32),
        )

    def init_paged_cache(self, batch: int, max_len: int, cfg: ArchConfig, *,
                         n_blocks: int, block_size: int,
                         dtype=jnp.bfloat16) -> PagedKVCache:
        """Shared KV block pool + per-slot block tables.

        Pool k/v carry a leading layer dim (``(n_layers, kvh, n_blocks,
        block_size, hd)``, head-major per layer — see :class:`PagedKVCache`)
        and ride whole through the layer scans; per-layer lengths scan
        with the blocks; the block table is layer-invariant (every layer
        mirrors the same allocation) so it is stored once and closed over
        by the scans.  Unmapped table entries hold the sentinel
        ``n_blocks``."""
        if cfg.window:
            raise UnsupportedCacheError(
                "paged KV cache requires global attention (cfg.window == 0)",
                roadmap_item="ring-buffer (sliding-window) caches in "
                "per-slot mode so hymba-family models can serve "
                "continuously")
        kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        max_table = -(-max_len // block_size)
        return PagedKVCache(
            k=jnp.zeros((self.n_layers, kvh, n_blocks, block_size, hd),
                        dtype),
            v=jnp.zeros((self.n_layers, kvh, n_blocks, block_size, hd),
                        dtype),
            table=jnp.full((batch, max_table), n_blocks, jnp.int32),
            length=jnp.zeros((self.n_layers, batch), jnp.int32),
        )

    def prefill(self, tokens: jax.Array, cache: KVCache, *,
                length: Optional[jax.Array] = None):
        """Returns logits for the LAST position + the filled cache.

        ``length`` (scalar or ``(batch,)`` int32) marks the true prompt
        length of right-padded prompts: logits are taken at ``length - 1``
        and the returned cache's counters are set to ``length`` so decode
        resumes there.  Sound for causal self-attention — padded positions
        never influence positions ``< length``, and decode overwrites each
        padded cache row before it becomes visible."""
        x = constrain_acts(self.embed(tokens))

        def body(x, xs):
            blk, c = xs
            fn = (lambda b, xx, cc: b.prefill(xx, cc))
            if self.remat:
                fn = jax.checkpoint(fn)
            y, c2 = fn(blk, x, c)
            return constrain_acts(y), c2

        x, new_cache = jax.lax.scan(body, x, (self.blocks, cache))
        # per-layer Attention.prefill emits scalar lengths; restore the INPUT
        # cache's layout (per-slot caches keep their (n_layers, batch) shape)
        if length is None:
            if cache.length.ndim == 2:
                new_cache = new_cache._replace(length=jnp.broadcast_to(
                    new_cache.length[:, None], cache.length.shape))
            return self._head(self.final_norm(x[:, -1:])), new_cache
        idx = jnp.asarray(length, jnp.int32)
        if idx.ndim == 1 and cache.length.ndim != 2:
            raise ValueError(
                "(batch,) prefill length requires a per_slot=True cache "
                f"(cache.length is {cache.length.shape})")
        rows = idx if idx.ndim else jnp.full((tokens.shape[0],), idx)
        last = jnp.take_along_axis(x, (rows - 1)[:, None, None], axis=1)
        logits = self._head(self.final_norm(last))
        # scalar broadcasts over any layout; (batch,) fans out over layers
        new_len = jnp.broadcast_to(idx if idx.ndim == 0 else idx[None, :],
                                   cache.length.shape)
        return logits, new_cache._replace(length=new_len)

    def _paged_scan(self, x, cache: PagedKVCache, step):
        """Run ``step(block, x, layer_cache, layer)`` over the layers.

        The pool k/v ride whole in the scan's carry, from the (donated)
        argument to the result, and each layer gets the whole pool with
        its index; only the blocks, the per-layer lengths and the layer
        index are scanned over.  So no layer slice of the pool is cut out
        and stacked back (a copy of the whole pool per call); the pool is
        read and written in place at ``[layer]``."""
        table = cache.table

        def body(carry, xs):
            x, k, v = carry
            blk, ln, layer = xs
            y, c2 = step(blk, x, PagedKVCache(k, v, table, ln), layer)
            return (constrain_acts(y), c2.k, c2.v), c2.length

        (x, k, v), ln = jax.lax.scan(
            body, (x, cache.k, cache.v),
            (self.blocks, cache.length, jnp.arange(self.n_layers)))
        return x, PagedKVCache(k, v, table, ln)

    def prefill_chunk(self, tokens: jax.Array, cache, *, slot: jax.Array,
                      offset: jax.Array, n_valid: jax.Array,
                      dst: Optional[jax.Array] = None,
                      need_logits: bool = True,
                      prefill_kernel: str = "reference"):
        """Consume one bucket-padded prompt chunk for slot ``slot``.

        ``tokens``: (1, W) int32 — ``n_valid`` real tokens starting at
        absolute position ``offset``, right-padded to the bucket width W.
        Works on both serving cache layouts (per-slot dense
        :class:`KVCache` and :class:`PagedKVCache`; for the paged layout
        ``dst`` carries the flat pool row per chunk position, sentinel for
        padding/cached-prefix positions — see
        :meth:`repro.nn.attention.Attention.prefill_chunk`).
        ``prefill_kernel`` picks the chunk attention implementation per
        layer (``"reference"`` dense gather vs ``"pallas"`` flash
        prefill-chunk kernel — see the same method).

        Returns ``(logits (1, vocab) at the chunk's LAST valid position,
        updated cache)`` — the engine only samples from the logits of a
        prompt's FINAL chunk, so it traces earlier chunks with
        ``need_logits=False`` (trace-time constant) and the final-norm +
        vocab-projection matmul drops out of the mid-prompt chunks
        entirely; those calls return ``(None, cache)``.
        """
        x = constrain_acts(self.embed(tokens))
        kw = dict(slot=slot, offset=offset, n_valid=n_valid,
                  prefill_kernel=prefill_kernel)

        if isinstance(cache, PagedKVCache):
            x, new_cache = self._paged_scan(
                x, cache, lambda blk, y, c, layer: blk.prefill_chunk(
                    y, c, dst=dst, layer=layer, **kw))
        else:
            def body(x, xs):
                blk, c = xs
                y, c2 = blk.prefill_chunk(x, c, **kw)
                return constrain_acts(y), c2

            x, new_cache = jax.lax.scan(body, x, (self.blocks, cache))
        if not need_logits:
            return None, new_cache
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        return self._head(self.final_norm(last))[:, 0], new_cache

    def decode(self, token: jax.Array, cache, *,
               decode_kernel: str = "reference"):
        """token: (batch, s) -> logits (batch, s, vocab) + updated cache.

        ``s == 1`` is the ordinary autoregressive step; ``s > 1`` is the
        multi-token step speculative verification uses (position ``j``
        attends rows ``<= pos + j``, so the logits equal a sequential
        ``s``-step decode's — see :meth:`repro.nn.attention.Attention.
        decode`).

        Accepts a dense :class:`KVCache` or a :class:`PagedKVCache` (see
        :meth:`_paged_scan` for how the paged pool rides through the
        layers); ``decode_kernel`` picks the paged implementation
        (``"reference"`` XLA scatter + dense gather vs ``"pallas"``
        in-place write + fused kernel — see
        :meth:`repro.nn.attention.Attention.decode`)."""
        x = constrain_acts(self.embed(token))

        if isinstance(cache, PagedKVCache):
            x, new_cache = self._paged_scan(
                x, cache, lambda blk, y, c, layer: blk.decode(
                    y, c, decode_kernel=decode_kernel, layer=layer))
            return self._head(self.final_norm(x)), new_cache

        def body(x, xs):
            blk, c = xs
            y, c2 = blk.decode(x, c)
            return constrain_acts(y), c2

        x, new_cache = jax.lax.scan(body, x, (self.blocks, cache))
        return self._head(self.final_norm(x)), new_cache
