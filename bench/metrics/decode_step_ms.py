"""Mean device time of one decode step program (``_decode``) in the
traced slice."""

import trace_reduce as tr
import work


def read(run):
    ev = tr.step_events(run.device_trace, tr.MODULES_LINE, work.DECODE)
    return sum(e[2] for e in ev) * 1e-6 / len(ev) if ev else None
