"""95th percentile of how late the client sent each request against its
due time: a starved generator shows here, not as a fast server."""

import numpy as np

import loadgen


def read(run):
    v = loadgen.lateness(run.records, run.end)
    return float(np.percentile(v, 95)) * 1e3 if v.size else None
