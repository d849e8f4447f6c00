"""Share of its roofline that the prefill-chunk attention kernel reached,
over the chunks of the traced slice."""

import costs
import work


def read(run):
    flops = nbytes = 0.0
    for _, chunks in work.steps(run):
        for o, n, _ in chunks:
            f, b = costs.chunk_attention_cost(run.conf, o, n)
            flops, nbytes = flops + f, nbytes + b
    return work.share(flops, nbytes, work.kernel_s(run, work.CHUNK_ATTN),
                      run)
