"""Work and device time of the engine steps on a mesh of chips: the
operations and bytes each step needs (with sizes from the configuration's
own reference, ``run.ref.dims``, which takes an untied head), and the
device time of each chip's plane of the trace.

The cost functions count the work as the algorithm needs it, as
``bench/costs.py`` does for one chip: attention reads the keys and values
up to each slot's position, only live decode rows and valid chunk rows
count, a multiply-add is two operations, element sizes are those of the
configuration's dtype.  ``model`` chips share every layer (the file's
``deployment``), each with an equal part of the heads, the MLP columns
and the vocabulary, so one chip's share of a kernel's work is the
model-wide work over ``model``.

Device time is read on every accelerator plane of the trace, one per
chip, each counting only the events that start inside an engine step the
benchmark recorded (the steps whose work it knows).
"""

from __future__ import annotations

import trace_reduce as tr
import work

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
#: the HLO names of the collectives XLA puts between the shards
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def dims(run) -> dict:
    return run.ref.dims(run.conf)


def _itemsize(run) -> int:
    return BYTES[run.conf["torch_dtype"]]


# -- operations and bytes, model-wide -----------------------------------------


def token_flops(d) -> float:
    """Projection and head operations of one token through the model."""
    per_layer = 2 * d["hidden"] * (2 * d["q_width"] + 2 * d["kv_width"]
                                   + 3 * d["inter"])
    return d["layers"] * per_layer + 2 * d["hidden"] * d["vocab"]


def attention_flops(d, keys: int) -> float:
    """Score and value products of queries attending ``keys`` keys in
    total, over every layer."""
    return 4 * d["layers"] * d["heads"] * d["head_dim"] * keys


def chunk_keys(offset: int, n: int) -> int:
    """Keys attended in total by a causal chunk of ``n`` queries at
    position ``offset``."""
    return n * offset + n * (n + 1) // 2


def step_flops(run) -> float:
    """Model operations of the recorded steps: each live decode slot's
    token at its context, each chunk's valid tokens (the head only for a
    prompt's final chunk, whose last token it samples from)."""
    d = dims(run)
    head = 2 * d["hidden"] * d["vocab"]
    total = 0.0
    for contexts, chunks in work.steps(run):
        total += (len(contexts) * token_flops(d)
                  + attention_flops(d, sum(contexts)))
        for o, n, final in chunks:
            total += (n * (token_flops(d) - head)
                      + attention_flops(d, chunk_keys(o, n))
                      + (head if final else 0))
    return total


def paged_attention_cost(run) -> tuple:
    """(operations, bytes) of the paged decode attention of the recorded
    steps, model-wide: each live slot's query against its ``k`` keys and
    values, in every layer."""
    d, b = dims(run), _itemsize(run)
    q_bytes = d["heads"] * d["head_dim"] * b
    kv_bytes = 2 * d["kv_heads"] * d["head_dim"] * b
    flops = nbytes = 0.0
    for contexts, _ in work.steps(run):
        flops += attention_flops(d, sum(contexts))
        nbytes += d["layers"] * sum(2 * q_bytes + k * kv_bytes
                                    for k in contexts)
    return flops, nbytes


def chunk_attention_cost(run) -> tuple:
    """(operations, bytes) of the prefill-chunk attention of the recorded
    steps, model-wide: ``n`` queries at ``offset`` against the resident
    prefix and the chunk's own keys and values, in every layer."""
    d, b = dims(run), _itemsize(run)
    q_bytes = d["heads"] * d["head_dim"] * b
    kv_bytes = 2 * d["kv_heads"] * d["head_dim"] * b
    flops = nbytes = 0.0
    for _, chunks in work.steps(run):
        for o, n, _ in chunks:
            flops += attention_flops(d, chunk_keys(o, n))
            nbytes += d["layers"] * (2 * n * q_bytes + (o + n) * kv_bytes)
    return flops, nbytes


# -- device time, chip by chip ------------------------------------------------


def plane_events(run, plane, line: str, patterns) -> list:
    """Events of one chip's ``line`` that match ``patterns`` and start
    inside a recorded step."""
    ev = [e for e in tr.line_events(plane, line) if tr.matches(e, patterns)]
    return tr.in_spans(ev, tr.step_spans(run.device_trace))


def per_chip_s(run, line: str, patterns) -> float:
    """Seconds of the matching events in the recorded steps, averaged over
    the chips' planes (0 where no chip ran one)."""
    planes = tr.device_planes(run.device_trace)
    if not planes:
        return 0.0
    return sum(sum(e[2] for e in plane_events(run, p, line, patterns))
               for p in planes) * 1e-9 / len(planes)


def is_collective(event) -> bool:
    return tr.op_name(event).startswith(COLLECTIVES)


def collective_s_per_decode(run):
    """Seconds each chip spends per decode step in the collectives of the
    decode program (ops whose HLO name starts with one of
    :data:`COLLECTIVES`, inside a ``decode_fn`` module event), averaged
    over the chips; ``None`` where no decode program or no collective
    ran."""
    total, steps = 0.0, 0
    for plane in tr.device_planes(run.device_trace):
        decodes = plane_events(run, plane, tr.MODULES_LINE, work.DECODE)
        spans = sorted((e[1], e[1] + e[2]) for e in decodes)
        ops = [e for e in tr.line_events(plane, tr.OPS_LINE)
               if is_collective(e)]
        total += sum(e[2] for e in tr.in_spans(ops, spans))
        steps += len(decodes)
    return total * 1e-9 / steps if steps and total else None


def shard_share(flops: float, nbytes: float, seconds: float, run):
    """Percent of one chip's roofline reached on that chip's share of the
    work (the model-wide work over ``model``), or ``None`` where nothing
    ran."""
    tp = dims(run)["model"]
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    return tr.roofline_share(flops / tp, nbytes / tp, seconds, run.peak)
