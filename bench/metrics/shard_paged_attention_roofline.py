"""Share of one chip's roofline that the paged decode attention kernel
reached on that chip's share of the work: the keys and values read up to
each live slot's position, over all heads and layers, divided by the
chips that share each layer, over the kernel's device time on one chip
(``_paged_attention_call`` events, every chip's plane read and
averaged)."""

import mesh_work
import trace_reduce as tr
import work


def read(run):
    flops, nbytes = mesh_work.paged_attention_cost(run)
    seconds = mesh_work.per_chip_s(run, tr.OPS_LINE, work.PAGED)
    return mesh_work.shard_share(flops, nbytes, seconds, run)
