"""From a profiler trace to the numbers the per-layer metrics read.

A trace is read once into a plain form, so that the reduction can be
checked on a small recorded trace (``bench/testdata/``)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, op], ...]}]}]}

``op`` is the event's ``tf_op`` or ``long_name`` stat where it has one
(the jitted wrapper and ``pallas_call`` path of a kernel), else ``""``.
Host planes hold the benchmark's own spans (``jax.profiler.
TraceAnnotation``) on one line per thread.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the traced window; the idle share is one minus busy
over the window.  Each idle gap is attributed to the innermost benchmark
span open on the host at its middle.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PEAKS = Path(__file__).resolve().parent / "peaks.json"
DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


# -- reading ------------------------------------------------------------------


def load_xplane(path) -> dict:
    """The plain form of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                op = ""
                if plane.name.startswith("/device:"):
                    stats = dict(e.stats)
                    op = str(stats.get("tf_op") or stats.get("long_name")
                             or "")
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), op])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


# -- selecting ----------------------------------------------------------------


def device_planes(trace: dict) -> list:
    """Planes of the accelerator chips (``/device:TPU:<n>``); never the
    host's, and never a ``/device:CUSTOM:...`` plane of the runtime."""
    return [p for p in trace["planes"] if DEVICE.match(p["name"])]


def line_events(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace: dict) -> list:
    """``(name, start_ns, end_ns)`` of the benchmark's own host spans."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            for e in line["events"]:
                if e[0].startswith(SPAN_PREFIX):
                    out.append((e[0], e[1], e[1] + e[2]))
    return out


def window(trace: dict) -> tuple:
    """``(start_ns, end_ns)`` of the traced window: the benchmark's
    ``bench.window`` span."""
    spans = [s for s in host_spans(trace) if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return spans[0][1], spans[0][2]


def op_name(event) -> str:
    """An op's own name: a TPU trace names each op by its whole HLO
    instruction (``%_paged_attention_call.6 = bf16[...] custom-call(...)``);
    the name is the part before `` = ``."""
    return event[0].split(" = ", 1)[0].lstrip("%")


def matches(event, patterns) -> bool:
    name = op_name(event)
    return any(p in name or p in event[3] for p in patterns)


# -- reducing -----------------------------------------------------------------


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(plane: dict, lo: float, hi: float) -> float:
    """Nanoseconds in ``[lo, hi]`` in which an operation ran."""
    ev = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
    return sum(e - s for s, e in union(((x[1], x[1] + x[2]) for x in ev),
                                       lo, hi))


def busy_s(trace: dict) -> tuple:
    """``(busy seconds, window seconds)`` of the traced window, busy time
    averaged over the chips."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace has no accelerator plane")
    busy = sum(busy_ns(p, lo, hi) for p in planes) / len(planes)
    return busy * 1e-9, (hi - lo) * 1e-9


def top_ops(plane: dict, lo: float, hi: float, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the device operations that took most
    time in the window, summed by name."""
    acc = defaultdict(float)
    for e in line_events(plane, OPS_LINE):
        if lo <= e[1] < hi:
            acc[_op_label(e)] += e[2] * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _op_label(e) -> str:
    """An op's name, with the kernel's jitted wrapper where the op is a
    kernel (``jit(_paged_attention_call)/pallas_call``)."""
    parts = e[3].split("/")
    if "pallas_call" in parts and parts.index("pallas_call") > 0:
        wrapper = parts[parts.index("pallas_call") - 1]
        return wrapper.removeprefix("jit(").removesuffix(")")
    name = op_name(e)
    return name.rstrip("0123456789.") or name


def idle_gaps(trace: dict, plane: dict, lo: float, hi: float,
              n: int = 10) -> list:
    """``[[label, seconds], ...]``: idle time of the device in the window,
    summed by the innermost benchmark span open on the host at the
    middle of each gap (``host: outside bench spans`` where none is)."""
    ev = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
    busy = union(((x[1], x[1] + x[2]) for x in ev), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = sorted(((s + e) / 2, e - s)
                  for s, e in zip(edges[::2], edges[1::2]) if e > s)
    spans = sorted((sp for sp in host_spans(trace) if sp[0] != WINDOW_SPAN),
                   key=lambda sp: sp[1])
    acc = defaultdict(float)
    active, i = [], 0
    for mid, length in gaps:  # sweep: spans open at each gap's middle
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] >= mid]
        label = (min(active, key=lambda sp: sp[2] - sp[1])[0] if active
                 else "host: outside bench spans")
        acc[label] += length * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


# -- peaks and rooflines ------------------------------------------------------


def peaks(device_kind: str, path=PEAKS) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Percent of the chip's roofline: the least time the work could take
    (the larger of FLOPs over peak FLOP/s and bytes over peak bandwidth)
    over the time it took."""
    if seconds <= 0:
        raise ValueError("no time to divide by")
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least / seconds


# -- the benchmark's steps ----------------------------------------------------

STEP_SPAN = "bench.engine_step"


def step_spans(trace: dict) -> list:
    """``(start_ns, end_ns)`` of the engine steps that ran wholly inside
    the traced window, in order: the steps whose work the benchmark
    recorded."""
    lo, hi = window(trace)
    return sorted((s, e) for name, s, e in host_spans(trace)
                  if name == STEP_SPAN and lo <= s and e <= hi)


def in_spans(events, spans) -> list:
    """The events that start inside one of ``spans`` (sorted)."""
    import bisect

    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] <= spans[i][1]:
            out.append(ev)
    return out


def step_events(trace: dict, line: str, patterns) -> list:
    """Events of the first device's ``line`` that match ``patterns`` and
    ran within a recorded step."""
    plane = device_planes(trace)[0]
    ev = [e for e in line_events(plane, line) if matches(e, patterns)]
    return in_spans(ev, step_spans(trace))
