"""Pallas TPU kernels for the serving/compute hot-spots.

* ``led_matmul`` — the paper's fused LED (low-rank) matmul ``(x @ A) @ B``
  (led_matmul.py kernel, ops.py jit wrappers + custom VJP).
* ``paged_attention`` — fused paged-attention decode: single-query
  attention streamed block-by-block from the shared KV pool through the
  per-slot block tables (paged_attention.py).
* ``chunk_attention`` — flash-style prefill-chunk attention: one slot's
  prompt chunk against its resident paged prefix + its own fresh K/V
  with offset-relative causal masking (chunk_attention.py;
  ``chunk_attention_dense`` serves the dense per-slot lane through the
  same kernel body via an identity block table).
* ``pool_write`` — the in-place write of a step's new K/V rows into one
  layer of the stacked paged pool, block by block by DMA
  (pool_write.py; its oracle is the XLA scatter it replaces).
* ``ref`` — pure-jnp oracles for all of them; the correctness references
  the interpret-mode CI matrix pins the kernels against (see README.md).
"""

from repro.kernels.chunk_attention import (chunk_attention,
                                           chunk_attention_dense)
from repro.kernels.ops import (default_interpret, led_matmul,
                               led_matmul_ref, led_matmul_trainable)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import chunk_attention_ref, paged_attention_ref

__all__ = ["chunk_attention", "chunk_attention_dense",
           "chunk_attention_ref", "default_interpret", "led_matmul",
           "led_matmul_ref", "led_matmul_trainable", "paged_attention",
           "paged_attention_ref"]
