"""95th percentile of the gaps between consecutive streamed tokens, over
every gap of every request in the window (client side)."""

import numpy as np

import loadgen


def read(run):
    v = loadgen.token_gaps(run.records, run.end)
    return float(np.percentile(v, 95)) * 1e3 if v.size else None
