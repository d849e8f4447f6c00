"""The system under test, as the benchmark drives it.

Builds the program's model from a configuration file and the
benchmark's weights (the way a checkpoint is loaded: the program's own
module tree, its leaves replaced by the given arrays), and the
``ContinuousEngine`` with the settings every cell serves with.  Nothing
here computes a result; the program does.

A configuration's ``program`` section names what to build:

- ``arch`` and ``replace``: ``repro.configs.get_config(arch).replace(**replace)``;
- ``keys``: configuration key -> ``ArchConfig`` field, each checked equal,
  so the program runs the sizes the file states;
- ``weights``: leaf path in the program's module tree -> weight name in
  ``bench/reference.py``'s :func:`weight_specs`.

A configuration with a ``factorize`` section is served as the paper's
``auto_fact`` leaves it: every ``Linear`` that ``factorize.exclude`` does
not name becomes an ``LED`` with ``fuse="auto"`` (the ``led_matmul``
kernel on a TPU), its factors ``A``, ``B`` the benchmark's weights
``<name>.A``, ``<name>.B``.
"""

from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def import_program():
    """Put the program's sources on the path; the benchmark lives beside
    them in a checkout."""
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program's sources are not at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the sizes the file states."""
    import_program()
    from repro.configs import get_config

    prog = conf["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("replace", {}))
    for key, field in prog["keys"].items():
        have = getattr(cfg, field)
        if have != conf[key]:
            raise ValueError(f"program config {field}={have!r} but the "
                             f"configuration states {key}={conf[key]!r}")
    return cfg


def load_model(conf: dict, cfg, weights: dict):
    """The program's model with the benchmark's ``weights`` as its leaves."""
    import jax

    import_program()
    from repro.models import build_model
    from repro.nn.linear import LED, Linear
    from repro.nn.module import map_modules

    shapes = jax.eval_shape(lambda k: build_model(k, cfg),
                            jax.random.PRNGKey(0))
    names = conf["program"]["weights"]
    fact = conf.get("factorize")
    exclude = fact["exclude"] if fact else []

    def leaf(path, like):
        name = names.get(path)
        if name is None:
            raise KeyError(f"no weight named for program leaf {path!r}")
        return _checked(weights[name], like, path)

    def visit(path, node):
        if isinstance(node, Linear):
            name = names.get(f"{path}.weight")
            if fact and not any(e in path for e in exclude):
                bias = (None if node.bias is None
                        else leaf(f"{path}.bias", node.bias))
                return LED(A=weights[f"{name}.A"], B=weights[f"{name}.B"],
                           bias=bias, fuse="auto")
            return Linear(weight=leaf(f"{path}.weight", node.weight),
                          bias=(None if node.bias is None
                                else leaf(f"{path}.bias", node.bias)))
        return node

    model = map_modules(shapes, visit)
    flat, tree = jax.tree_util.tree_flatten_with_path(model)
    out = []
    for kp, x in flat:
        if isinstance(x, jax.ShapeDtypeStruct):
            path = ".".join(_key(k) for k in kp)
            x = leaf(path, x)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _key(k) -> str:
    import jax

    if isinstance(k, jax.tree_util.GetAttrKey):
        return k.name
    return str(getattr(k, "idx", getattr(k, "key", k)))


def _checked(arr, like, path):
    if arr.shape != like.shape or arr.dtype != like.dtype:
        raise ValueError(f"{path}: weight {arr.shape} {arr.dtype}, program "
                         f"wants {like.shape} {like.dtype}")
    return arr


def make_engine(model, cfg, engine: dict, seed: int):
    """``ContinuousEngine`` as every cell serves: paged bf16 pool in the
    model's dtype, both Pallas kernels, prefix reuse on, 256-token chunks;
    buckets, prefill budget, pool size and retention at their defaults.
    ``engine`` is the traffic mix's ``slots`` and ``max_len``."""
    import jax.numpy as jnp

    import_program()
    from repro.serve import ContinuousEngine

    return ContinuousEngine(
        model, cfg, batch=engine["slots"], max_len=engine["max_len"],
        max_prompt_len=engine["max_len"] - 1,
        cache_dtype=jnp.dtype(cfg.dtype), seed=seed & 0x7FFFFFFF,
        kv_layout="paged", decode_kernel="pallas", prefill_kernel="pallas",
        chunk_size=256, prefix_reuse=True)
