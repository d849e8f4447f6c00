"""Share of the traced window in which no operation ran on the device."""

import trace_reduce as tr


def read(run):
    busy, window = tr.busy_s(run.device_trace)
    return 100.0 * (1 - busy / window)
