"""Plain float32 reference of the qwen2 decoder, dense and LED, and the
weights the benchmark makes from a seed.

Imports nothing of the program under test.  The configuration is the
benchmark's own JSON file (Hugging Face key names, see
``bench/configs/``); the weights are made here from the seed, in the type
they are served in, and the same arrays are handed to the program
(``bench/program.py``) and to this reference.

The forward pass follows the published Qwen2 description: token
embedding, per layer RMSNorm -> grouped-query attention with QKV bias and
rotary embeddings (split halves, base ``rope_theta``) -> residual ->
RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm and the output head
tied to the embedding.  Every matrix product runs at ``HIGHEST``
precision in float32.  An ``LED`` projection is ``(x @ A) @ B + bias``,
the paper's factorized linear layer.

It is computed in blocks so that it fits beside nothing else on one chip:
the layers run under a scan with each layer's weights upcast inside the
body, attention runs over blocks of query rows, and the head over blocks
of positions, reduced at once to the three numbers per position the
comparison needs (the best logit, the logit of the target token and the
arg-max).

``quant="fp8"`` is the control: every projection and the head take their
operands rounded to float8 (e4m3) with one scale per tensor (weights) or
per row (activations), accumulating in float32 — the precision one step
below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# the projections of one layer: name -> (input width, output width), as
# keys of :func:`dims`
PROJECTIONS = {
    "q_proj": ("hidden", "q_width"), "k_proj": ("hidden", "kv_width"),
    "v_proj": ("hidden", "kv_width"), "o_proj": ("q_width", "hidden"),
    "gate_proj": ("hidden", "inter"), "up_proj": ("hidden", "inter"),
    "down_proj": ("inter", "hidden"),
}
BIASED = ("q_proj", "k_proj", "v_proj")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole seed: the bits above 32 are
    folded in, since ``PRNGKey`` keeps only the low 32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def dims(conf: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    hidden = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    head_dim = conf.get("head_dim") or hidden // heads
    kv = conf["num_key_value_heads"]
    d = {"layers": conf["num_hidden_layers"], "hidden": hidden,
         "heads": heads, "kv_heads": kv, "head_dim": head_dim,
         "q_width": heads * head_dim, "kv_width": kv * head_dim,
         "inter": conf["intermediate_size"], "vocab": conf["vocab_size"],
         "eps": conf["rms_norm_eps"], "theta": conf["rope_theta"],
         "std": conf["initializer_range"]}
    if not conf.get("tie_word_embeddings"):
        raise ValueError("this reference has a tied output head only")
    return d


def ranks(conf: dict) -> dict:
    """Rank of each factorized projection (empty for a dense model): the
    ratio ``factorize.rank`` of the smaller side, as ``auto_fact`` resolves
    a float rank with its gate off."""
    fact = conf.get("factorize")
    if not fact:
        return {}
    d = dims(conf)
    out = {}
    for name, (i, o) in PROJECTIONS.items():
        m, n = d[i], d[o]
        out[name] = max(1, int(fact["rank"] * min(m, n)))
    return out


def weight_specs(conf: dict) -> dict:
    """``name -> (shape, kind)`` of every weight; ``kind`` is ``"normal"``
    with a standard deviation, ``"norm"`` (a norm scale near 1) or
    ``"bias"``.  Per-layer weights carry a leading layer axis."""
    d = dims(conf)
    n_layers = d["layers"]
    specs = {"embed": ((d["vocab"], d["hidden"]), ("normal", d["std"])),
             "final_norm": ((d["hidden"],), ("norm",)),
             "input_norm": ((n_layers, d["hidden"]), ("norm",)),
             "post_norm": ((n_layers, d["hidden"]), ("norm",))}
    r = ranks(conf)
    for name, (i, o) in PROJECTIONS.items():
        m, n = d[i], d[o]
        if name in r:
            specs[f"{name}.A"] = ((n_layers, m, r[name]),
                                  ("normal", 1 / math.sqrt(m)))
            specs[f"{name}.B"] = ((n_layers, r[name], n),
                                  ("normal", 1 / math.sqrt(r[name])))
        else:
            specs[name] = ((n_layers, m, n), ("normal", 1 / math.sqrt(m)))
        if name in BIASED:
            specs[f"{name}.bias"] = ((n_layers, n), ("bias",))
    return specs


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight, made on the default device in one jitted call from
    the seed, in the configuration's dtype."""
    specs = weight_specs(conf)
    dtype = jnp.dtype(conf["torch_dtype"])

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, dtype)
            if kind[0] == "normal":
                out[name] = z * jnp.asarray(kind[1], dtype)
            elif kind[0] == "norm":  # scales near 1, so a dropped one shows
                out[name] = 1 + z * jnp.asarray(0.1, dtype)
            else:
                out[name] = z * jnp.asarray(0.1, dtype)
        return out

    return jax.jit(make)(seed_key(seed))


# -- forward ------------------------------------------------------------------


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (``None``: one per tensor), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def _proj(p, name, x, quant):
    if f"{name}.A" in p:
        y = _mm(_mm(x, p[f"{name}.A"], quant), p[f"{name}.B"], quant)
    else:
        y = _mm(x, p[name], quant)
    if f"{name}.bias" in p:
        y = y + p[f"{name}.bias"]
    return y


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (T, heads, head_dim); rotation of the split halves."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv  # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, block):
    """Causal GQA over blocks of ``block`` query rows.
    q: (T, H, hd); k, v: (T, KV, hd) -> (T, H * hd)."""
    t, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    kpos = jnp.arange(t)
    qb = q.reshape(t // block, block, kv, g, hd)

    def one(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k,
                       precision=HIGHEST) / math.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
        return o.reshape(block, h * hd)

    out = jax.lax.map(one, (jnp.arange(t // block), qb))
    return out.reshape(t, h * hd)


def _hidden(w, tokens, d, quant, block):
    """Final-normed hidden states of one sequence, float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = f32(w["embed"][tokens])
    stacked = {k: a for k, a in w.items()
               if k not in ("embed", "final_norm")}

    def layer(x, p):
        p = {k: f32(a) for k, a in p.items()}
        h = _rms(x, p["input_norm"], d["eps"])
        q = _proj(p, "q_proj", h, quant).reshape(t, d["heads"], -1)
        k = _proj(p, "k_proj", h, quant).reshape(t, d["kv_heads"], -1)
        v = _proj(p, "v_proj", h, quant).reshape(t, d["kv_heads"], -1)
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        x = x + _proj(p, "o_proj", _attention(q, k, v, block), quant)
        h = _rms(x, p["post_norm"], d["eps"])
        gate = _proj(p, "gate_proj", h, quant)
        up = _proj(p, "up_proj", h, quant)
        x = x + _proj(p, "down_proj", jax.nn.silu(gate) * up, quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _rms(x, f32(w["final_norm"]), d["eps"])


@functools.partial(jax.jit, static_argnames=("d", "quant", "block"))
def _scores(w, tokens, targets, *, d, quant, block):
    """Per position: (best logit, logit of ``targets``, arg-max), float32.
    ``d`` is :func:`dims` as a sorted tuple of items."""
    d = dict(d)
    t = tokens.shape[0]
    x = _hidden(w, tokens, d, quant, block)
    head = w["embed"].astype(jnp.float32).T

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
    return _mm(x, weights["embed"].astype(jnp.float32).T, "none")


def scores(conf: dict, weights: dict, tokens, targets=None, *,
           quant: str = "none", block: int = 256, pad_to: int = 512):
    """Run the reference over one sequence ``tokens`` and return numpy
    arrays ``(best, target, argmax)`` for positions ``0..len-2``.  The
    target of position ``i`` is ``targets[i]``, by default the next token
    ``tokens[i + 1]``.

    The sequence is right-padded to ``pad_to`` times a power of two, so
    that few lengths compile; causal masking keeps the padding out of
    every real position."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.size
    targets = tokens[1:] if targets is None else np.asarray(targets,
                                                             np.int32)
    padded = pad_to
    while padded < n:
        padded *= 2
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    tgt = np.zeros(padded, np.int32)
    tgt[:n - 1] = targets[:n - 1]
    d = tuple(sorted(dims(conf).items()))
    out = _scores(weights, jnp.asarray(toks), jnp.asarray(tgt), d=d,
                  quant=quant, block=block)
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
