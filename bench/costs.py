"""Operations and bytes that the serving steps need, from the
configuration's own shapes.

Counted as the algorithm needs them, not as a kernel happens to do them:
attention reads the keys and values up to each slot's position (not
whole block tables), only live decode rows and valid chunk rows count,
and an ``LED`` projection counts as its two factors.  A multiply-add is
two operations.  Element sizes are those of the configuration's dtype.
"""

from __future__ import annotations

from reference import PROJECTIONS, dims, ranks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _itemsize(conf) -> int:
    return BYTES[conf["torch_dtype"]]


def projection_shapes(conf) -> list:
    """``(in, out, rank or None)`` of every projection of one layer."""
    d, r = dims(conf), ranks(conf)
    return [(d[i], d[o], r.get(name)) for name, (i, o) in PROJECTIONS.items()]


def matmul_flops_per_token(conf) -> float:
    """Projection operations of one token through every layer."""
    per_layer = sum(2 * (m * r + r * n) if r else 2 * m * n
                    for m, n, r in projection_shapes(conf))
    return dims(conf)["layers"] * per_layer


def head_flops(conf) -> float:
    d = dims(conf)
    return 2 * d["hidden"] * d["vocab"]


def attention_flops(conf, queries) -> float:
    """Score and value products of queries that each attend ``k`` keys,
    over every layer; ``queries`` is an iterable of ``k``."""
    d = dims(conf)
    return 4 * d["layers"] * d["heads"] * d["head_dim"] * sum(queries)


def chunk_keys(offset: int, n: int) -> int:
    """Keys attended in total by a causal chunk of ``n`` queries starting
    at position ``offset``."""
    return n * offset + n * (n + 1) // 2


# -- the step programs --------------------------------------------------------


def decode_step_flops(conf, contexts) -> float:
    """One decode step over live slots whose new tokens sit at context
    lengths ``contexts`` (keys attended, the new one included)."""
    n = len(contexts)
    return (n * (matmul_flops_per_token(conf) + head_flops(conf))
            + attention_flops(conf, contexts))


def chunk_flops(conf, offset: int, n: int, final: bool) -> float:
    """One prefill chunk of ``n`` valid tokens at ``offset``; the final
    chunk of a prompt also runs the head for its last token."""
    return (n * matmul_flops_per_token(conf)
            + attention_flops(conf, [chunk_keys(offset, n)])
            + (head_flops(conf) if final else 0))


# -- the kernels --------------------------------------------------------------


def paged_attention_cost(conf, contexts) -> tuple:
    """(operations, bytes) of the paged decode attention over every layer:
    each live slot's query against its ``k`` keys and values."""
    d, b = dims(conf), _itemsize(conf)
    q_bytes = d["heads"] * d["head_dim"] * b
    kv_bytes = 2 * d["kv_heads"] * d["head_dim"] * b
    nbytes = d["layers"] * sum(2 * q_bytes + k * kv_bytes for k in contexts)
    return attention_flops(conf, contexts), nbytes


def chunk_attention_cost(conf, offset: int, n: int) -> tuple:
    """(operations, bytes) of the prefill-chunk attention over every
    layer: ``n`` queries at ``offset`` against the resident prefix and the
    chunk's own keys and values."""
    d, b = dims(conf), _itemsize(conf)
    q_bytes = d["heads"] * d["head_dim"] * b
    kv_bytes = 2 * d["kv_heads"] * d["head_dim"] * b
    nbytes = d["layers"] * (2 * n * q_bytes + (offset + n) * kv_bytes)
    return attention_flops(conf, [chunk_keys(offset, n)]), nbytes
