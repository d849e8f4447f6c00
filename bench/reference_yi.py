"""Plain float32 reference of the llama decoder (Yi-9B), and the weights
the benchmark makes from a seed, already placed over the deployment's
mesh.

Imports nothing of the program under test.  The configuration is the
benchmark's own JSON file (Hugging Face key names, see ``bench/configs/``)
with a ``deployment`` section that names the mesh the model is served on
(``{"data": 1, "model": 4}``: every layer's heads and MLP columns, and the
vocabulary, divided over four chips).

The forward pass follows the published llama description, as Yi uses it:
token embedding, per layer RMSNorm -> grouped-query attention without
biases, with rotary embeddings (split halves, base ``rope_theta``) ->
residual -> RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm and an
output head of its own (``tie_word_embeddings`` false).  ``rms_norm_eps``
and ``rope_theta`` come from the file.  The primitives (RMSNorm, RoPE,
causal GQA over blocks of query rows, the matrix product at ``HIGHEST``
precision and its float8 control) are those of ``bench/reference.py``;
what differs is the head, the missing biases and the placement.

The weights are made in one jitted call whose outputs are sharded over
the first ``data x model`` devices as the Megatron layout places them
(column-parallel q/k/v/gate/up and head, row-parallel o/down, a
vocab-parallel embedding, replicated norms), so no device ever holds the
whole model; the forward pass runs under plain ``jax.jit`` on those
arrays, each layer's weights upcast inside the layer scan, and reduces
each position at once to the three numbers the comparison needs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference import _attention, _mm, _rms, _rope, seed_key

# projection name -> (input width, output width, the dim split over
# "model"): column-parallel layers split their output, row-parallel ones
# their input
PROJECTIONS = {
    "q_proj": ("hidden", "q_width", -1), "k_proj": ("hidden", "kv_width", -1),
    "v_proj": ("hidden", "kv_width", -1), "o_proj": ("q_width", "hidden", -2),
    "gate_proj": ("hidden", "inter", -1), "up_proj": ("hidden", "inter", -1),
    "down_proj": ("inter", "hidden", -2),
}


def dims(conf: dict) -> dict:
    """The sizes the reference and the benchmark's cost functions need,
    from the configuration file; ``model`` is the number of chips that
    share each layer."""
    hidden = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    head_dim = conf.get("head_dim") or hidden // heads
    kv = conf["num_key_value_heads"]
    mesh = conf["deployment"]["mesh"]
    return {"layers": conf["num_hidden_layers"], "hidden": hidden,
            "heads": heads, "kv_heads": kv, "head_dim": head_dim,
            "q_width": heads * head_dim, "kv_width": kv * head_dim,
            "inter": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "eps": conf["rms_norm_eps"], "theta": float(conf["rope_theta"]),
            "std": conf["initializer_range"],
            "tied": bool(conf["tie_word_embeddings"]),
            "data": mesh["data"], "model": mesh["model"]}


def mesh(conf: dict) -> Mesh:
    """The deployment's ``{data, model}`` mesh over the first devices."""
    d = dims(conf)
    n = d["data"] * d["model"]
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"the deployment needs {n} devices, "
                           f"{len(devices)} found")
    return Mesh(np.asarray(devices[:n]).reshape(d["data"], d["model"]),
                ("data", "model"))


def weight_specs(conf: dict) -> dict:
    """``name -> (shape, kind, placement)`` of every weight; ``kind`` is
    ``("normal", std)`` or ``("norm",)`` (a norm scale near 1), and the
    placement a ``PartitionSpec`` over the deployment's mesh.  Per-layer
    weights carry a leading layer axis.  A dim that does not divide the
    model axis stays whole."""
    d = dims(conf)
    n, tp = d["layers"], d["model"]

    def split(shape, dim):
        spec = [None] * len(shape)
        if tp > 1 and shape[dim] % tp == 0:
            spec[dim] = "model"
        return P(*spec)

    vocab, hidden = d["vocab"], d["hidden"]
    specs = {
        "embed": ((vocab, hidden), ("normal", d["std"])),
        "lm_head": ((hidden, vocab), ("normal", 1 / math.sqrt(hidden))),
        "final_norm": ((hidden,), ("norm",)),
        "input_norm": ((n, hidden), ("norm",)),
        "post_norm": ((n, hidden), ("norm",))}
    placed = {"embed": 0, "lm_head": -1}
    for name, (i, o, dim) in PROJECTIONS.items():
        specs[name] = ((n, d[i], d[o]), ("normal", 1 / math.sqrt(d[i])))
        placed[name] = dim
    return {name: (shape, kind, split(shape, placed[name])
                   if name in placed else P(*[None] * len(shape)))
            for name, (shape, kind) in specs.items()}


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight, made in one jitted call from the seed in the
    configuration's dtype, each written straight into its shards on the
    deployment's mesh."""
    specs = weight_specs(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    m = mesh(conf)

    def make(key):
        out = {}
        for i, (name, (shape, kind, _)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            if kind[0] == "normal":
                out[name] = z * jnp.asarray(kind[1], dtype)
            else:  # norm scales near 1, so a dropped one shows
                out[name] = 1 + z * jnp.asarray(0.1, dtype)
        return out

    shardings = {name: NamedSharding(m, spec)
                 for name, (_, _, spec) in specs.items()}
    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


# -- forward ------------------------------------------------------------------


def _hidden(w, tokens, d, quant, block):
    """Final-normed hidden states of one sequence, float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = f32(w["embed"][tokens])
    stacked = {k: a for k, a in w.items()
               if k not in ("embed", "lm_head", "final_norm")}

    def layer(x, p):
        p = {k: f32(a) for k, a in p.items()}
        h = _rms(x, p["input_norm"], d["eps"])
        q = _mm(h, p["q_proj"], quant).reshape(t, d["heads"], -1)
        k = _mm(h, p["k_proj"], quant).reshape(t, d["kv_heads"], -1)
        v = _mm(h, p["v_proj"], quant).reshape(t, d["kv_heads"], -1)
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        x = x + _mm(_attention(q, k, v, block), p["o_proj"], quant)
        h = _rms(x, p["post_norm"], d["eps"])
        act = jax.nn.silu(_mm(h, p["gate_proj"], quant)) * _mm(
            h, p["up_proj"], quant)
        return x + _mm(act, p["down_proj"], quant), None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _rms(x, f32(w["final_norm"]), d["eps"])


@functools.partial(jax.jit, static_argnames=("d", "quant", "block"))
def _scores(w, tokens, targets, *, d, quant, block):
    """Per position: (best logit, logit of ``targets``, arg-max), float32.
    ``d`` is :func:`dims` as a sorted tuple of items."""
    d = dict(d)
    t = tokens.shape[0]
    x = _hidden(w, tokens, d, quant, block)
    head = w["lm_head"].astype(jnp.float32)

    def rows(args):
        xb, tb = args
        logits = _mm(xb, head, quant)
        return (logits.max(-1),
                jnp.take_along_axis(logits, tb[:, None], -1)[:, 0],
                jnp.argmax(logits, -1).astype(jnp.int32))

    out = jax.lax.map(rows, (x.reshape(t // block, block, -1),
                             targets.reshape(t // block, block)))
    return tuple(o.reshape(t) for o in out)


def logits(conf: dict, weights: dict, tokens, *, block: int = 8):
    """Every logit of every position of a short sequence, float32 (for
    checking the reference itself at small sizes)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.shape[0] % block:
        raise ValueError(f"length {tokens.shape[0]} is not a multiple of "
                         f"{block}")
    x = _hidden(weights, tokens, dims(conf), "none", block)
    return _mm(x, weights["lm_head"].astype(jnp.float32), "none")


def scores(conf: dict, weights: dict, tokens, targets=None, *,
           quant: str = "none", block: int = 256, pad_to=None):
    """Run the reference over one sequence ``tokens`` and return numpy
    arrays ``(best, target, argmax)`` for positions ``0..len-2``.  The
    target of position ``i`` is ``targets[i]``, by default the next token
    ``tokens[i + 1]``.

    The sequence is right-padded to ``pad_to`` times a power of two, by
    default the model's context (``max_position_embeddings``), so that
    one length compiles; causal masking keeps the padding out of every
    real position."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.size
    targets = tokens[1:] if targets is None else np.asarray(targets,
                                                             np.int32)
    padded = pad_to or conf["max_position_embeddings"]
    while padded < n:
        padded *= 2
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    tgt = np.zeros(padded, np.int32)
    tgt[:n - 1] = targets[:n - 1]
    d = tuple(sorted(dims(conf).items()))
    out = _scores(weights, jnp.asarray(toks), jnp.asarray(tgt), d=d,
                  quant=quant, block=min(block, padded))
    return tuple(np.asarray(a)[:n - 1] for a in out)


def served_gaps(conf: dict, weights: dict, prompt, served, *,
                quant: str = "none", **kw) -> np.ndarray:
    """Gaps of one request: for each served token, how far its reference
    logit lies below the reference's best at that position.  With
    ``quant`` set (the control), the gap of the token that the quantized
    forward puts first at each of those positions is read instead."""
    seq = list(prompt) + list(served)
    lo = len(prompt) - 1
    if quant == "none":
        best, at, _ = scores(conf, weights, seq, **kw)
    else:
        _, _, top = scores(conf, weights, seq, quant=quant, **kw)
        best, at, _ = scores(conf, weights, seq, targets=top, **kw)
    return (best - at)[lo:]
