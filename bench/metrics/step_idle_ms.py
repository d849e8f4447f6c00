"""Device idle milliseconds per engine step: the gaps in the first
device's busy time inside each of the program's ``serve.step`` spans that
lies wholly inside the traced window, averaged over those steps
(``bench/program_spans.py``).  Silent for a program that opens no such
span."""

import program_spans


def read(run):
    split = program_spans.step_idle(run.device_trace)
    return None if split is None else split["idle_ms_per_step"]
