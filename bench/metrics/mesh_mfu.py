"""Model FLOP utilization of the whole mesh in the step programs: the
operations the tokens they processed need (prompt tokens computed and
tokens decoded, model-wide), over their device time on one chip (the
``decode_fn`` and chunk programs, every chip's plane read and averaged)
times the chip's peak times the cell's chips."""

import mesh_work
import trace_reduce as tr
import work


def read(run):
    seconds = mesh_work.per_chip_s(run, tr.MODULES_LINE,
                                   work.DECODE + work.CHUNK)
    flops = mesh_work.step_flops(run)
    if not seconds or not flops:
        return None
    return 100.0 * flops / (seconds * run.peak["bf16_flops"]
                            * run.cell["chips"])
