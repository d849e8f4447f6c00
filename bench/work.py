"""The work of the engine steps recorded in a traced run, in operations
and bytes (``bench/costs.py``), beside their device time in the trace
(``bench/trace_reduce.py``).

The metric readers share these sums.  Device time counts only the
programs and kernels that ran inside the recorded steps, so work and time
cover the same steps.
"""

from __future__ import annotations

import costs
import trace_reduce as tr

DECODE = ("decode_fn",)             # ContinuousEngine._decode
CHUNK = ("lambda",)                 # ContinuousEngine._chunk_last / _mid
PAGED = ("_paged_attention_call",)
CHUNK_ATTN = ("_chunk_attention_call",)
LED = ("led_matmul",)


def device_s(run, line: str, patterns) -> float:
    ev = tr.step_events(run.device_trace, line, patterns)
    return sum(e[2] for e in ev) * 1e-9


def program_s(run, patterns) -> float:
    return device_s(run, tr.MODULES_LINE, patterns)


def kernel_s(run, patterns) -> float:
    return device_s(run, tr.OPS_LINE, patterns)


def steps(run) -> list:
    return run.work.get("steps", [])


def decode_flops(run) -> float:
    return sum(costs.decode_step_flops(run.conf, ctx)
               for ctx, _ in steps(run) if ctx)


def chunk_flops(run) -> float:
    return sum(costs.chunk_flops(run.conf, o, n, final)
               for _, chunks in steps(run) for o, n, final in chunks)


def chunk_tokens(run) -> int:
    return sum(n for _, chunks in steps(run) for _, n, _ in chunks)


def share(flops: float, nbytes: float, seconds: float, run):
    """Percent of the roofline, or ``None`` where nothing ran."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    return tr.roofline_share(flops, nbytes, seconds, run.peak)
