"""Model FLOP utilization of the prefill chunk programs alone: the
operations of the prompt tokens they computed over their device time
times the chip's peak."""

import work


def read(run):
    s = work.program_s(run, work.CHUNK)
    flops = work.chunk_flops(run)
    if not s or not flops:
        return None
    return 100.0 * flops / (s * run.peak["bf16_flops"])
