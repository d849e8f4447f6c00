"""``auto_fact`` — the paper's one-line automatic factorization API.

    from repro import auto_fact
    fact_model = auto_fact(model, rank=128, solver='svd', num_iter=50)

Walks the module tree, replaces every ``Linear`` with an ``LED`` and every
``Conv1D``/``Conv2D`` with a ``CED1D``/``CED2D`` whenever the resolved rank
passes the paper's ``r < r_max`` gate.  Supports:

* ``rank`` as an absolute int or a float ratio of each layer's ``r_max``
  (the paper's dynamic rank);
* ``solver`` ∈ {'random', 'svd', 'snmf'} (random = factorization-by-design);
* ``submodules`` / ``exclude`` path filters (the paper's filtering feature);
* stacked weights (layer-scanned or expert-stacked ``Linear``s) — solvers are
  batched over the leading axes, so e.g. all 384 experts of kimi-k2
  factorize in one call.

Being a pure pytree→pytree function it composes with jit/pjit sharding.
"""

from __future__ import annotations

import fnmatch
import zlib
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.rank import Rank, r_max, resolve_rank
from repro.core.solvers import get_solver
from repro.nn.conv import CED1D, CED2D, Conv1D, Conv2D
from repro.nn.linear import LED, Linear
from repro.nn.module import Module, map_modules


@dataclass
class FactReport:
    """What auto_fact did, layer by layer."""

    # (path, kind, m, n, r, rel_err) — rel_err is the relative Frobenius
    # reconstruction error ||W - A@B||_F / ||W||_F over the whole (possibly
    # stacked) weight, so a bad solve is localizable to its layer.
    entries: list = field(default_factory=list)
    skipped: list = field(default_factory=list)  # (path, reason)
    params_before: int = 0
    params_after: int = 0

    @property
    def compression(self) -> float:
        if self.params_after == 0:
            return 1.0  # nothing factorized → no compression, not 0x
        return self.params_before / self.params_after

    def summary(self) -> str:
        lines = [f"auto_fact: {len(self.entries)} layers factorized, "
                 f"{len(self.skipped)} skipped"]
        lines += [f"  [fact] {p} ({kind}) {m}x{n} -> r={r} rel_err={e:.4f}"
                  for p, kind, m, n, r, e in self.entries]
        lines += [f"  [skip] {p}: {why}" for p, why in self.skipped]
        if self.params_before:
            lines.append(
                f"  target params: {self.params_before:,} -> "
                f"{self.params_after:,} ({self.compression:.2f}x)")
        return "\n".join(lines)


def _matches(path: str, patterns: Optional[Sequence[str]]) -> bool:
    if patterns is None:
        return True
    return any(p in path or fnmatch.fnmatch(path, p) for p in patterns)


def _layer_key(base_key, path: str):
    return jax.random.fold_in(base_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _rel_err(w, a, b) -> float:
    """Relative Frobenius reconstruction error of W ≈ A @ B (stack-aware;
    one stacked matrix at a time, so the float32 residual is one
    matrix's)."""
    *_, m, n = w.shape
    r = a.shape[-1]

    def sq_norms(wab):
        w32, a32, b32 = (x.astype(jnp.float32) for x in wab)
        return jnp.sum((a32 @ b32 - w32) ** 2), jnp.sum(w32 ** 2)

    num, den = jax.lax.map(sq_norms, (w.reshape(-1, m, n),
                                      a.reshape(-1, m, r),
                                      b.reshape(-1, r, n)))
    return float(jnp.sqrt(num.sum())
                 / jnp.maximum(jnp.sqrt(den.sum()), 1e-30))


def _resolve_ungated(rank: Rank, m: int, n: int) -> int:
    """Rank resolution when the r_max gate is off: float ratios scale
    min(m, n) (so ``rank=1.0`` is an exact full-rank factorization) and
    int ranks are clamped to min(m, n)."""
    if isinstance(rank, bool) or not isinstance(rank, (int, float)):
        raise TypeError(f"rank must be int or float, got {type(rank)}")
    if isinstance(rank, float):
        if not 0.0 < rank <= 1.0:
            raise ValueError(f"float rank must be in (0, 1], got {rank}")
        return max(1, int(rank * min(m, n)))
    if rank < 1:
        raise ValueError(f"int rank must be >= 1, got {rank}")
    return min(rank, min(m, n))


def auto_fact(
    module: Module,
    rank: Rank,
    *,
    solver: str = "svd",
    num_iter: int = 50,
    key: Optional[jax.Array] = None,
    submodules: Optional[Sequence[str]] = None,
    exclude: Optional[Sequence[str]] = None,
    factorize_linear: bool = True,
    factorize_conv: bool = True,
    fuse: str = "auto",
    gate: bool = True,
    return_report: bool = False,
):
    """Factorize a model. See module docstring. Returns the new model
    (and a :class:`FactReport` when ``return_report=True``).

    ``gate=False`` disables the paper's ``r < r_max`` break-even check and
    resolves float ranks against ``min(m, n)`` instead of ``r_max``, so
    ``rank=1.0, solver='svd'`` yields an exact (to fp) full-rank LED —
    useful for differential testing, never for compression."""
    solve = get_solver(solver)
    if solver == "random" and key is None:
        key = jax.random.PRNGKey(0)
    report = FactReport()

    def visit(path: str, node: Module):
        if not isinstance(node, (Linear, Conv1D, Conv2D)):
            return node  # keep recursing
        if not _matches(path, submodules) or (exclude and _matches(path, exclude)):
            report.skipped.append((path, "filtered"))
            return node

        if isinstance(node, Linear):
            if not factorize_linear:
                return node
            *stack, m, n = node.weight.shape
        else:
            if not factorize_conv:
                return node
            if isinstance(node, Conv1D):
                c_in, c_out, s = node.weight.shape
                m, n = c_in * s, c_out
            else:
                c_in, c_out, kh, kw = node.weight.shape
                m, n = c_in * kh * kw, c_out
            stack = []

        if gate:
            r = resolve_rank(rank, m, n)
            if r >= r_max(m, n):
                report.skipped.append(
                    (path, f"rank {r} >= r_max {r_max(m, n):.1f} ({m}x{n})"))
                return node
        else:
            r = _resolve_ungated(rank, m, n)

        lkey = _layer_key(key, path) if key is not None else None
        report.params_before += node.weight.size
        if isinstance(node, Linear):
            a, b = solve(node.weight, r, key=lkey, num_iter=num_iter)
            new = LED(A=a, B=b, bias=node.bias, fuse=fuse)
            report.entries.append(
                (path, "linear", m, n, r, _rel_err(node.weight, a, b)))
        elif isinstance(node, Conv1D):
            w_mat = jnp.transpose(node.weight, (0, 2, 1)).reshape(m, n)
            a_mat, b_mat = solve(w_mat, r, key=lkey, num_iter=num_iter)
            a = a_mat.reshape(c_in, s, r).transpose(0, 2, 1)  # (Cin, r, S)
            b = b_mat[:, :, None]  # (r, Cout, 1)
            new = CED1D(A=a, B=b, bias=node.bias, stride=node.stride,
                        padding=node.padding)
            report.entries.append(
                (path, "conv1d", m, n, r, _rel_err(w_mat, a_mat, b_mat)))
        else:
            w_mat = jnp.transpose(node.weight, (0, 2, 3, 1)).reshape(m, n)
            a_mat, b_mat = solve(w_mat, r, key=lkey, num_iter=num_iter)
            a = a_mat.reshape(c_in, kh, kw, r).transpose(0, 3, 1, 2)
            b = b_mat[:, :, None, None]
            new = CED2D(A=a, B=b, bias=node.bias, stride=node.stride,
                        padding=node.padding)
            report.entries.append(
                (path, "conv2d", m, n, r, _rel_err(w_mat, a_mat, b_mat)))
        report.params_after += a.size + b.size
        return new

    fact = map_modules(module, visit)
    return (fact, report) if return_report else fact


def defactorize(module: Module):
    """Inverse convenience: collapse every LED/CED back to a dense layer."""

    def visit(path: str, node: Module):
        if isinstance(node, (LED, CED1D, CED2D)):
            return node.materialize()
        return node

    return map_modules(module, visit)
