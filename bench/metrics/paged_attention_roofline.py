"""Share of its roofline that the paged decode attention kernel reached:
the least time for the keys and values read up to each live slot's
position, over the kernel's device time."""

import costs
import work


def read(run):
    flops = nbytes = 0.0
    for ctx, _ in work.steps(run):
        if ctx:
            f, b = costs.paged_attention_cost(run.conf, ctx)
            flops, nbytes = flops + f, nbytes + b
    return work.share(flops, nbytes, work.kernel_s(run, work.PAGED), run)
