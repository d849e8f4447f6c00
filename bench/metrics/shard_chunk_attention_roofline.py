"""Share of one chip's roofline that the prefill-chunk attention kernel
reached on that chip's share of the work: the chunks' queries against
their resident prefix and their own keys and values, over all heads and
layers, divided by the chips that share each layer, over the kernel's
device time on one chip (``_chunk_attention_call`` events, every chip's
plane read and averaged)."""

import mesh_work
import trace_reduce as tr
import work


def read(run):
    flops, nbytes = mesh_work.chunk_attention_cost(run)
    seconds = mesh_work.per_chip_s(run, tr.OPS_LINE, work.CHUNK_ATTN)
    return mesh_work.shard_share(flops, nbytes, seconds, run)
