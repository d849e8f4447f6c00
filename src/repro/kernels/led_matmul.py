"""Fused LED (low-rank) matmul Pallas TPU kernel: ``y = (x @ A) @ B``.

TPU-native adaptation of the paper's LED layer (DESIGN.md §2): executed as
two back-to-back dense matmuls, the rank-``r`` intermediate ``t = x @ A``
round-trips through HBM (2·M·R·bytes of traffic) and the second matmul
launches from cold VMEM.  This kernel fuses both GEMMs so ``t`` lives in a
**VMEM scratch accumulator** and never touches HBM.

Grid layout: ``(i over M tiles, j over N tiles, k over K tiles)``, all
sequential ("arbitrary") so the scratch persists across steps:

  * ``j == 0``: accumulate ``t[i] += x[i,k] @ A[k]`` over the k-steps
    (fp32 accumulation on the MXU).
  * ``k == last``: emit ``y[i,j] = t[i] @ B[j]``.
  * ``j > 0``: the x/A index maps freeze at their last block, so Pallas'
    revisiting optimization skips the HBM→VMEM copies; only ``B[j]`` streams.

Block shapes default to MXU-aligned (multiples of 128 on the matmul dims);
``R`` (the rank, ≤ a few hundred by construction — Greenformer's r_max gate)
stays resident as a whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _led_kernel(x_ref, a_ref, b_ref, y_ref, t_ref, *, n_k: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(j == 0)
    def _accumulate():
        t_ref[...] += jnp.dot(
            x_ref[...], a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        y_ref[...] = jnp.dot(
            t_ref[...], b_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def led_matmul_2d(
    x: jax.Array,  # (M, K)
    a: jax.Array,  # (K, R)
    b: jax.Array,  # (R, N)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    m, kdim = x.shape
    _, r = a.shape
    _, n = b.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    if m % bm or n % bn or kdim % bk:
        raise ValueError(
            f"led_matmul_2d requires divisible dims, got M={m}%{bm} "
            f"N={n}%{bn} K={kdim}%{bk} (pad in ops.led_matmul)")
    n_i, n_j, n_k = m // bm, n // bn, kdim // bk

    def x_map(i, j, k):
        # freeze at the last k-block once j > 0 → revisiting skips the copy
        return (i, jnp.where(j == 0, k, n_k - 1))

    def a_map(i, j, k):
        return (jnp.where(j == 0, k, n_k - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_i, n_j, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bk, r), a_map),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_led_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, a, b)
