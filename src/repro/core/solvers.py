"""Factorization solvers: random, SVD, and semi-NMF (SNMF).

Every solver maps a weight matrix ``W ∈ R^{..., m, n}`` (arbitrary leading
*stack* axes — layer-stacked or expert-stacked weights are factorized in one
batched call) to a pair ``(A ∈ R^{..., m, r}, B ∈ R^{..., r, n})`` with
``W ≈ A @ B``.

* ``random`` — fresh initialization at the target rank; per the paper it is
  only suitable for *factorization-by-design* (it does not approximate W).
* ``svd``    — truncated SVD; the optimal rank-r approximation in Frobenius
  norm. The singular values are split symmetrically: ``A = U·√Σ, B = √Σ·Vᵀ``.
  Stacked weights are solved one matrix at a time, so the float32
  workspace is one matrix's, not the whole stack's.
* ``snmf``   — semi-non-negative MF (Ding, Li & Jordan 2010): ``W ≈ A·B`` with
  ``B ≥ 0`` and ``A`` unconstrained, fitted by ``num_iter`` multiplicative
  updates.  Jittable (``lax.fori_loop``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

EPS = 1e-8


def random_solver(w: jax.Array, rank: int, *, key: jax.Array,
                  num_iter: int = 0) -> tuple[jax.Array, jax.Array]:
    del num_iter
    *stack, m, n = w.shape
    ka, kb = jax.random.split(key)
    # lecun-style scaling so that var(A@B x) matches var(W x) at init
    a = jax.random.normal(ka, (*stack, m, rank), w.dtype) / jnp.sqrt(m).astype(w.dtype)
    b = jax.random.normal(kb, (*stack, rank, n), w.dtype) / jnp.sqrt(rank).astype(w.dtype)
    return a, b


def _thin_svd(w: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Thin SVD of one float32 matrix.  A wide or tall matrix is first
    reduced by QR to its square triangular factor, so every matrix with the
    same short side runs the same SVD program: on a TPU each SVD shape is
    minutes of compile."""
    m, n = w.shape
    if m < n:
        q, r = jnp.linalg.qr(w.T)  # w = rᵀ qᵀ
        u, s, vt = jnp.linalg.svd(r.T)
        return u, s, jnp.matmul(vt, q.T, precision="highest")
    if m > n:
        q, r = jnp.linalg.qr(w)  # w = q r
        u, s, vt = jnp.linalg.svd(r)
        return jnp.matmul(q, u, precision="highest"), s, vt
    return jnp.linalg.svd(w)


def svd_solver(w: jax.Array, rank: int, *, key: Optional[jax.Array] = None,
               num_iter: int = 0) -> tuple[jax.Array, jax.Array]:
    del key, num_iter
    *stack, m, n = w.shape
    a, b = [], []
    for x in w.reshape(-1, m, n):  # one float32 workspace at a time
        u, s, vt = _thin_svd(x.astype(jnp.float32))
        sq = jnp.sqrt(s[:rank])
        a.append((u[:, :rank] * sq).astype(w.dtype))
        b.append((sq[:, None] * vt[:rank]).astype(w.dtype))
    return (jnp.stack(a).reshape(*stack, m, rank),
            jnp.stack(b).reshape(*stack, rank, n))


def snmf_solver(w: jax.Array, rank: int, *, key: Optional[jax.Array] = None,
                num_iter: int = 50) -> tuple[jax.Array, jax.Array]:
    """Semi-NMF: W ≈ F·Gᵀ with G ≥ 0 (so A=F, B=Gᵀ ≥ 0).

    Multiplicative updates from Ding, Li & Jordan (2010), SVD-seeded for
    fast convergence.
    """
    dtype = w.dtype
    wf = w.astype(jnp.float32)
    *_, m, n = wf.shape

    # SVD-based seeding: G0 = |Vᵀ·√Σ|, strictly feasible (non-negative).
    a0, b0 = svd_solver(wf, rank)
    g = jnp.abs(jnp.swapaxes(b0, -1, -2)) + EPS  # (..., n, r)

    def pos(x):
        return (jnp.abs(x) + x) * 0.5

    def neg(x):
        return (jnp.abs(x) - x) * 0.5

    def body(_, g):
        # F = W G (Gᵀ G)⁻¹
        gtg = jnp.swapaxes(g, -1, -2) @ g  # (..., r, r)
        eye = jnp.eye(rank, dtype=jnp.float32)
        f = jnp.linalg.solve(gtg + EPS * eye, jnp.swapaxes(wf @ g, -1, -2))
        f = jnp.swapaxes(f, -1, -2)  # (..., m, r)
        # G <- G * sqrt( [ (WᵀF)+ + G (FᵀF)- ] / [ (WᵀF)- + G (FᵀF)+ ] )
        wtf = jnp.swapaxes(wf, -1, -2) @ f  # (..., n, r)
        ftf = jnp.swapaxes(f, -1, -2) @ f  # (..., r, r)
        num = pos(wtf) + g @ neg(ftf)
        den = neg(wtf) + g @ pos(ftf)
        g = g * jnp.sqrt((num + EPS) / (den + EPS))
        return g

    g = jax.lax.fori_loop(0, num_iter, body, g)
    gtg = jnp.swapaxes(g, -1, -2) @ g
    eye = jnp.eye(rank, dtype=jnp.float32)
    f = jnp.swapaxes(jnp.linalg.solve(gtg + EPS * eye,
                                      jnp.swapaxes(wf @ g, -1, -2)), -1, -2)
    return f.astype(dtype), jnp.swapaxes(g, -1, -2).astype(dtype)


SOLVERS: dict[str, Callable] = {
    "random": random_solver,
    "svd": svd_solver,
    "snmf": snmf_solver,
}


def get_solver(name: str) -> Callable:
    try:
        return SOLVERS[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; available: {sorted(SOLVERS)}") from None
