"""Sharding rules: Megatron tensor parallelism + expert parallelism + LED
factor boundary specs + ZeRO/FSDP fallbacks.

``spec_for_param`` maps a dotted parameter path + shape to a
``PartitionSpec`` on a ``{data[, pod], model}`` mesh:

* column-parallel projections (q/k/v, up/gate, mamba in_proj, lm_head)
  shard their OUTPUT dim on "model"; their biases shard with the output;
* row-parallel projections (o_proj, down_proj, mamba out_proj) shard their
  INPUT dim on "model"; their biases are replicated (added post-reduce);
* LED factors shard at the low-rank boundary: a column-parallel layer keeps
  ``A`` replicated and shards ``B``'s output dim, a row-parallel layer
  shards ``A``'s input dim and keeps ``B`` replicated — the rank-r
  intermediate is never partitioned;
* stacked experts shard the expert axis on "model" (expert parallelism);
* the embedding table is vocab-parallel;
* any dim that does not divide its mesh axes is replicated instead
  (e.g. hymba's vocab 32001 on a 16-way TP mesh);
* ``fsdp=True`` additionally shards the first eligible remaining dim of
  LARGE params over the data axes (ZeRO-3 style).

``constrain_acts`` is a no-op outside an ``activation_mesh`` context, so
models call it unconditionally and single-device tests/benches never touch
device state.

``kernel_axes`` names the axes the serving kernels split over when they
run shard-local, by the same rules as the pool's placement; ``mesh_of``
finds the mesh a model's weights are committed to.
"""

from __future__ import annotations

import math
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Minimum element count before FSDP bothers sharding a param over data.
FSDP_MIN_SIZE = 1 << 20

_COLUMN = {"q_proj", "k_proj", "v_proj", "up_proj", "gate_proj", "in_proj",
           "lm_head"}
_ROW = {"o_proj", "down_proj", "out_proj"}


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _data_entry(axes: Sequence[str]):
    return axes[0] if len(axes) == 1 else tuple(axes)


def batch_spec(mesh) -> P:
    """Batch-dim spec over every data-parallel mesh axis."""
    return P(_data_entry(_data_axes(mesh)))


def spec_for_param(path: str, shape: Tuple[int, ...], mesh,
                   fsdp: bool = False) -> P:
    """PartitionSpec for one parameter (see module docstring for rules)."""
    tp = mesh.shape.get("model", 1)
    data_axes = _data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    nd = len(shape)
    spec: list = [None] * nd
    parts = path.strip(".").split(".")
    leaf = parts[-1]
    owner = parts[-2] if len(parts) > 1 else ""

    if ".experts." in f".{path}." and nd >= 3:
        e_ax = nd - 3  # (..., E, in, out) / (..., E, in, r)
        if tp > 1 and shape[e_ax] % tp == 0:
            spec[e_ax] = "model"
    elif owner == "embed" and leaf == "weight":
        if tp > 1 and shape[0] % tp == 0:  # vocab-parallel table
            spec[0] = "model"
    elif owner in _COLUMN:
        if leaf in ("weight", "B", "bias") and tp > 1 and shape[-1] % tp == 0:
            spec[-1] = "model"  # output dim; A stays replicated
    elif owner in _ROW:
        if leaf in ("weight", "A") and nd >= 2 and tp > 1 \
                and shape[-2] % tp == 0:
            spec[-2] = "model"  # input dim; bias/B stay replicated
    # everything else (norms, routers, ssm params, pos embeddings): replicated

    if fsdp and dp > 1 and math.prod(shape) >= FSDP_MIN_SIZE:
        for i in range(nd):
            if spec[i] is None and shape[i] % dp == 0:
                spec[i] = _data_entry(data_axes)
                break
    return P(*spec)


def model_shardings(model, mesh, *, fsdp: bool = False):
    """NamedSharding tree mirroring ``model`` (arrays or SDS stand-ins)."""

    def _path_str(key_path) -> str:
        out = []
        for k in key_path:
            if hasattr(k, "name"):
                out.append(str(k.name))
            elif hasattr(k, "key"):
                out.append(str(k.key))
            elif hasattr(k, "idx"):
                out.append(str(k.idx))
            else:
                out.append(str(k).strip(".[]'\""))
        return ".".join(out)

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, spec_for_param(_path_str(kp), leaf.shape, mesh, fsdp=fsdp)),
        model)


def data_sharding(mesh, shape: Tuple[int, ...]) -> NamedSharding:
    """Shard dim 0 (batch) over the data axes; replicate the rest."""
    data_axes = _data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    spec: list = [None] * len(shape)
    if shape and dp > 1 and shape[0] % dp == 0:
        spec[0] = _data_entry(data_axes)
    return NamedSharding(mesh, P(*spec))


def cache_specs(cache, mesh):
    """PartitionSpec tree for a decode/prefill cache (see cache_shardings).

    Two layouts are distinguished by structure:

    * **paged** (a NamedTuple with a ``table`` field): the block pool k/v
      are (layers, kv_heads, n_blocks, block_size, head_dim) — the pool is
      GLOBAL over data (every data shard holds the full pool; the host-side
      allocator hands out block ids with no notion of placement) and its
      kv-head dim shards over "model".  The block table (batch, max_table)
      and write frontier (layers, batch) shard their batch dim over data.
    * **dense / ring / ssm** (everything else): per-slot lanes are
      (layers, batch, ...) so dim 1 shards over data, and any trailing
      (..., kv_heads, head_dim) lane shards its head dim over "model".

    Any non-divisible dim — e.g. GQA kv_heads=3 on a 2-way model axis —
    falls back to replication for that dim.
    """
    tp = mesh.shape.get("model", 1)
    data_axes = _data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    data = _data_entry(data_axes) if data_axes else None

    fields = getattr(cache, "_fields", None)
    if fields is not None and "table" in fields:
        def pool(leaf) -> P:
            s: list = [None] * len(leaf.shape)
            if len(leaf.shape) >= 4 and tp > 1 and leaf.shape[-4] % tp == 0:
                s[-4] = "model"
            return P(*s)

        def batch_dim(leaf, dim: int) -> P:
            s: list = [None] * len(leaf.shape)
            if dp > 1 and leaf.shape[dim] % dp == 0:
                s[dim] = data
            return P(*s)

        return type(cache)(
            k=pool(cache.k),
            v=pool(cache.v),
            table=batch_dim(cache.table, 0),
            length=batch_dim(cache.length, len(cache.length.shape) - 1),
        )

    def spec(leaf) -> P:
        shape = leaf.shape
        s: list = [None] * len(shape)
        if len(shape) >= 2 and dp > 1 and shape[1] % dp == 0:
            s[1] = data
        if len(shape) >= 4 and tp > 1 and shape[-2] % tp == 0:
            s[-2] = "model"
        return P(*s)

    return jax.tree_util.tree_map(spec, cache)


def kernel_axes(mesh, kv_heads: int, batch: int):
    """``(heads, rows)``: the mesh axes the serving kernels split over
    when they run shard-local (``repro.nn.attention``).  KV heads go over
    "model" where they divide it, exactly as :func:`cache_specs` places
    the pool's kv-head dim, so each model shard attends its own KV heads
    with their query heads; slots go over the data axes where they divide
    them, as the block tables do.  ``None`` where a dim stays whole (the
    pool is replicated there, and every shard computes it alike)."""
    tp = mesh.shape.get("model", 1)
    data_axes = _data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    heads = "model" if tp > 1 and kv_heads % tp == 0 else None
    rows = _data_entry(data_axes) if dp > 1 and batch % dp == 0 else None
    return heads, rows


def mesh_of(tree):
    """The mesh ``tree``'s arrays are committed to: that of the first leaf
    placed by a ``NamedSharding`` over more than one device, else ``None``
    (a model built or loaded on one device)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return sh.mesh
    return None


def cache_shardings(cache, mesh):
    """Decode/prefill cache shardings: batch over data, heads over model.

    NamedSharding tree over :func:`cache_specs` — see there for the
    paged-vs-dense layout rules.  KV lanes are
    (layers, batch, slots, kv_heads, head_dim); SSM/conv states are
    (layers, batch, ...).  Any non-divisible dim is replicated."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), cache_specs(cache, mesh),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation constraints
# ---------------------------------------------------------------------------

# (mesh, seq_parallel) inside an activation_mesh scope.  A ContextVar, not
# a module global: BackgroundServer traces engine steps off the main
# thread, and a module global set on one thread would leak the mesh into
# (or hide it from) traces running concurrently on another.  Each thread
# starts with a fresh context, so scopes are strictly per-thread/per-task.
_ACTIVE: ContextVar[Optional[tuple]] = ContextVar(
    "repro_activation_mesh", default=None)


@contextmanager
def activation_mesh(mesh, seq_parallel: bool = False):
    """Enable activation sharding constraints for traces under this scope."""
    token = _ACTIVE.set((mesh, seq_parallel))
    try:
        yield mesh
    finally:
        _ACTIVE.reset(token)


def active_activation_mesh() -> Optional[tuple]:
    """The ``(mesh, seq_parallel)`` of the innermost :func:`activation_mesh`
    scope on THIS thread/task — exactly what :func:`constrain_acts` will
    read — or ``None`` outside any scope."""
    return _ACTIVE.get()


def constrain_acts(x: jax.Array) -> jax.Array:
    """Constrain (batch, seq, d_model) activations between blocks.

    Batch shards over the data axes; with sequence parallelism the seq dim
    additionally shards over "model".  Outside an :func:`activation_mesh`
    scope this is the identity (returns ``x`` itself)."""
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, seq_parallel = active
    data_axes = _data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    tp = mesh.shape.get("model", 1)
    spec: list = [None] * x.ndim
    if x.ndim >= 1 and dp > 1 and x.shape[0] % dp == 0:
        spec[0] = _data_entry(data_axes)
    if seq_parallel and x.ndim >= 2 and tp > 1 and x.shape[1] % tp == 0:
        spec[1] = "model"
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


__all__ = ["batch_spec", "spec_for_param", "model_shardings", "data_sharding",
           "cache_specs", "cache_shardings", "kernel_axes", "mesh_of",
           "activation_mesh", "active_activation_mesh", "constrain_acts",
           "FSDP_MIN_SIZE"]
