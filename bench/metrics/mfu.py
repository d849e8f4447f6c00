"""Model FLOP utilization of the step programs: the operations the tokens
they processed need (prompt tokens computed and tokens decoded, LED
layers counted as LED), over their summed device time times the chip's
peak."""

import work


def read(run):
    s = work.program_s(run, work.DECODE + work.CHUNK)
    flops = work.decode_flops(run) + work.chunk_flops(run)
    if not s or not flops:
        return None
    return 100.0 * flops / (s * run.peak["bf16_flops"])
