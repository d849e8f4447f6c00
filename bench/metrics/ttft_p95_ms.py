"""95th percentile of time to first token, from when each request was due,
over every request due in the window (client side)."""

import numpy as np

import loadgen


def read(run):
    v = loadgen.ttfts(run.records, run.end)
    return float(np.percentile(v, 95)) * 1e3 if v.size else None
