"""The program's own host spans against the device trace: how long the
device sat idle inside each engine step, and under which phase of it.

``repro.serve`` opens ``serve.*`` host spans (``jax.profiler.
TraceAnnotation``): ``serve.step`` around each engine step and one span
per phase inside it (``serve.admit``, ``serve.table_flush``,
``serve.prefill_chunk``, ``serve.bind``, ``serve.decode``,
``serve.host_sync``, ``serve.emit``).  ``trace_reduce.host_spans`` keeps
only the benchmark's own ``bench.*`` spans; this module reads the
program's.  A trace of a program that opens no ``serve.*`` span has no
steps here, and its readers report nothing.

    python bench/program_spans.py .bench_trace/<cell>

prints the split of the newest trace under a directory as JSON.
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict

import trace_reduce as tr

PREFIX = "serve."
STEP = "serve.step"


def serve_spans(trace: dict) -> list:
    """``(name, start_ns, end_ns)`` of every ``serve.*`` host span."""
    out = []
    for p in trace.get("planes", []):
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            for e in line["events"]:
                if e[0].startswith(PREFIX):
                    out.append((e[0], e[1], e[1] + e[2]))
    return out


def step_idle(trace: dict):
    """Device idle inside the ``serve.step`` spans that lie wholly inside
    the traced window: ``{"steps": n, "idle_ms_per_step": ms, "by_phase":
    {span: ms per step}}``, or ``None`` where the trace has no window, no
    device or no such step.

    Idle is the gaps in the union of the first device's ops, clipped to
    each step; each gap goes to the innermost ``serve.*`` span open at
    its middle (``serve.step`` itself where no phase is), so the phases
    sum to the whole."""
    planes = tr.device_planes(trace) if trace.get("planes") else []
    if not planes:
        return None
    try:
        lo, hi = tr.window(trace)
    except ValueError:  # no bench.window span
        return None
    spans = serve_spans(trace)
    steps = sorted((s, e) for n, s, e in spans
                   if n == STEP and lo <= s and e <= hi)
    if not steps:
        return None
    ev = (tr.line_events(planes[0], tr.OPS_LINE)
          or tr.line_events(planes[0], tr.MODULES_LINE))
    busy = tr.union(((x[1], x[1] + x[2]) for x in ev), lo, hi)
    starts = [b[0] for b in busy]
    phases = sorted((sp[1], sp[2], sp[0]) for sp in spans if sp[0] != STEP)
    phase_starts = [p[0] for p in phases]
    acc = defaultdict(float)
    for s, e in steps:
        inner = phases[bisect.bisect_left(phase_starts, s):
                       bisect.bisect_right(phase_starts, e)]
        for g0, g1 in _gaps(busy, starts, s, e):
            mid = (g0 + g1) / 2
            open_ = [p for p in inner if p[0] <= mid <= p[1]]
            name = (min(open_, key=lambda p: p[1] - p[0])[2] if open_
                    else STEP)
            acc[name] += g1 - g0
    n = len(steps)
    return {"steps": n,
            "idle_ms_per_step": sum(acc.values()) * 1e-6 / n,
            "by_phase": {k: v * 1e-6 / n for k, v in
                         sorted(acc.items(), key=lambda kv: -kv[1])}}


def _gaps(busy: list, starts: list, lo: float, hi: float) -> list:
    """The ``[start, end]`` pieces of ``[lo, hi]`` that no interval of
    ``busy`` (merged, sorted, with their ``starts``) covers."""
    out, t = [], lo
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(busy) and busy[i][0] < hi:
        b0, b1 = busy[i]
        if b1 > t:
            if b0 > t:
                out.append((t, b0))
            t = b1
        i += 1
    if t < hi:
        out.append((t, hi))
    return out


if __name__ == "__main__":
    split = step_idle(tr.load_xplane(tr.find_xplane(sys.argv[1])))
    print(json.dumps(split, indent=1))
