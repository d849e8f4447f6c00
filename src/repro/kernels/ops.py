"""jit'd public wrappers around the Pallas kernels.

``led_matmul`` accepts arbitrary leading batch axes, pads every matmul dim up
to the block grid, dispatches to the fused kernel, and slices the result
back.  On non-TPU backends it runs the kernel in ``interpret=True`` mode so
tests exercise the *same* kernel body everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.led_matmul import led_matmul_2d
from repro.kernels.ref import led_matmul_ref


def default_interpret() -> bool:
    """Shared interpret-mode policy for every Pallas kernel in the repo,
    chosen by platform alone: compiled on TPU, interpreted (the *same*
    kernel bodies, so tests exercise them everywhere) on any other
    backend."""
    return jax.default_backend() != "tpu"


def _pad_to(v: int, b: int) -> int:
    return (-v) % b


def led_matmul(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused ``(x @ A) @ B``. x: (..., K); a: (..., K, R); b: (..., R, N).

    a/b may carry matching leading stack axes (layer-scanned or
    expert-stacked auto_fact weights); each stack slice must pair with the
    same-index leading axis of x, and the 2D kernel is vmapped over them.
    """
    if interpret is None:
        interpret = default_interpret()
    if a.ndim > 2:
        if a.shape[:-2] != b.shape[:-2]:
            raise ValueError(
                f"stack axes of a {a.shape} and b {b.shape} must match")
        if x.shape[: a.ndim - 2] != a.shape[:-2]:
            raise ValueError(
                f"x leading axes {x.shape} must match stack axes {a.shape}")
        return jax.vmap(
            lambda xx, aa, bb: led_matmul(
                xx, aa, bb, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret))(x, a, b)
    *lead, kdim = x.shape
    r = a.shape[-1]
    n = b.shape[-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, kdim)

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    pm, pn, pk = _pad_to(m, bm), _pad_to(n, bn), _pad_to(kdim, bk)
    xp = jnp.pad(x2, ((0, pm), (0, pk))) if (pm or pk) else x2
    ap = jnp.pad(a, ((0, pk), (0, 0))) if pk else a
    bp = jnp.pad(b, ((0, 0), (0, pn))) if pn else b

    y = led_matmul_2d(xp, ap, bp, block_m=bm, block_n=bn, block_k=bk,
                      interpret=interpret)
    if pm or pn:
        y = y[:m, :n]
    return y.reshape(*lead, n)


# ---------------------------------------------------------------------------
# Differentiable wrapper: the Pallas kernel is forward-only, so training
# uses a custom VJP whose backward re-derives the three low-rank gradients —
# and dx = (dy @ Bᵀ) @ Aᵀ is itself a low-rank matmul, so it reuses the
# fused kernel.  dA/dB recompute the rank-r intermediate (cheap: M·R) rather
# than saving it (the kernel's whole point is never materializing it in HBM).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def led_matmul_trainable(x, a, b):
    return led_matmul(x, a, b)


def _led_fwd(x, a, b):
    return led_matmul(x, a, b), (x, a, b)


def _led_bwd(res, dy):
    x, a, b = res
    if a.ndim > 2:
        # stacked factors: the hand-derived gradients below are 2D-only, so
        # fall back to autodiff through the (stack-aware) jnp oracle
        _, vjp = jax.vjp(led_matmul_ref, x, a, b)
        return vjp(dy)
    *lead, kdim = x.shape
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, kdim).astype(jnp.float32)
    dy2 = dy.reshape(m, b.shape[-1]).astype(jnp.float32)
    dt = dy2 @ b.astype(jnp.float32).T  # (M, R)
    da = (x2.T @ dt).astype(a.dtype)
    t = x2 @ a.astype(jnp.float32)  # recomputed rank-r intermediate
    db = (t.T @ dy2).astype(b.dtype)
    dx = led_matmul(dy, jnp.swapaxes(b, -1, -2),
                    jnp.swapaxes(a, -1, -2))  # fused low-rank backward
    return dx.reshape(x.shape).astype(x.dtype), da, db


led_matmul_trainable.defvjp(_led_fwd, _led_bwd)

__all__ = ["default_interpret", "led_matmul", "led_matmul_ref",
           "led_matmul_trainable"]
