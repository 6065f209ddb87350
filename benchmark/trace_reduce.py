"""Reduction of a profiler trace to the numbers the per-layer metrics read.

One pass over the ``.xplane.pb`` the JAX profiler wrote (read with
``jax.profiler.ProfileData``, nothing else) gives a ``Trace``: the device
operations of each chip as ``(name, start_ns, end_ns)``, and the host spans
(TraceAnnotation / StepTraceAnnotation names) the same way. Everything else
(busy union, idle gaps and whom they belong to, self time of a span) is
arithmetic on those lists, so the tests run it on a small recorded list.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
# host spans the reduction attributes idle gaps to, innermost first on ties
HOST_SPANS = ("fed_drain", "fed_client_phase", "fed_server_phase",
              "fed_offload_gather", "fed_round", "bench_loader_wait",
              "bench_begin_round", "bench_apply_server",
              "bench_finish_round", "bench_telemetry", "bench_drain",
              "bench_submit", "bench_window")


@dataclass
class Trace:
    device_ops: dict = field(default_factory=dict)   # chip -> [(name, s, e)]
    host_spans: list = field(default_factory=list)   # [(name, s, e)]
    planes: list = field(default_factory=list)       # [(plane, line, count)]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str, span_names=HOST_SPANS) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    wanted = set(span_names)
    out = Trace()
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            n = 0
            if is_device and line.name == OPS_LINE:
                ops = out.device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
                    n += 1
            elif not is_device:
                for ev in line.events:
                    n += 1
                    if ev.name in wanted:
                        out.host_spans.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
            out.planes.append((plane.name, line.name, n))
    for ops in out.device_ops.values():
        ops.sort(key=lambda o: o[1])
    out.host_spans.sort(key=lambda s: s[1])
    return out


# ---- arithmetic on intervals --------------------------------------------

def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals):
    """Merge ``(start, end)`` intervals; returns the disjoint sorted list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy, lo, hi):
    """The complement of a disjoint sorted ``busy`` list inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def window_of(trace: Trace, name="bench_window"):
    """[start, end] of the traced window: the harness's own span around the
    timed call."""
    spans = [(s, e) for n, s, e in trace.host_spans if n == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_by_chip(trace: Trace, lo, hi) -> dict:
    return {chip: union(clip([(s, e) for _, s, e in ops], lo, hi))
            for chip, ops in trace.device_ops.items()}


def busy_seconds(trace: Trace, lo, hi) -> float:
    """Seconds in which an operation ran, averaged over the chips traced."""
    per_chip = busy_by_chip(trace, lo, hi)
    if not per_chip:
        return 0.0
    return sum(total(b) for b in per_chip.values()) / len(per_chip) / 1e9


def op_seconds(trace: Trace, lo, hi, match=None) -> dict:
    """Device seconds by operation name (averaged over chips), for the
    operations ``match`` accepts."""
    out = {}
    chips = max(len(trace.device_ops), 1)
    for ops in trace.device_ops.values():
        for name, s, e in ops:
            if e <= lo or s >= hi or (match and not match(name)):
                continue
            out[name] = out.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return {k: v / chips for k, v in out.items()}


def span_seconds(trace: Trace, name, lo, hi) -> float:
    return total(clip([(s, e) for n, s, e in trace.host_spans if n == name],
                      lo, hi)) / 1e9


def intersect(a, b):
    """Intersection of two disjoint sorted interval lists (two pointers)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """The parts of disjoint sorted ``a`` that disjoint sorted ``b`` leaves."""
    out, j = [], 0
    for s, e in a:
        at = s
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if e > at:
            out.append((at, e))
    return out


def _spans(trace: Trace, names, lo, hi):
    names = {names} if isinstance(names, str) else set(names)
    return union(clip([(s, e) for n, s, e in trace.host_spans if n in names],
                      lo, hi))


def self_seconds(trace: Trace, name, children, lo, hi) -> float:
    """Time inside ``name`` spans not covered by any ``children`` span."""
    return total(subtract(_spans(trace, name, lo, hi),
                          _spans(trace, children, lo, hi))) / 1e9


BUBBLE_NS = 20_000


def attribute_gaps(trace: Trace, lo, hi, order=HOST_SPANS,
                   bubble_ns=BUBBLE_NS) -> dict:
    """Idle seconds of the first chip by what the host was doing: each part
    of a gap goes to the innermost host span that covers it (``order`` lists
    inner spans before the spans that contain them), the rest to ``other``.
    Gaps shorter than ``bubble_ns`` are the device's own pauses between two
    operations of one program, whatever the host does meanwhile: they are
    summed under ``op_bubbles``."""
    per_chip = busy_by_chip(trace, lo, hi)
    if not per_chip:
        return {}
    idle = gaps(per_chip[sorted(per_chip)[0]], lo, hi)
    out = {}
    bubbles = total([g for g in idle if g[1] - g[0] < bubble_ns])
    if bubbles:
        out["op_bubbles"] = bubbles / 1e9
        idle = [g for g in idle if g[1] - g[0] >= bubble_ns]
    for name in order:
        spans = _spans(trace, name, lo, hi)
        taken = total(intersect(idle, spans))
        if taken:
            out[name] = taken / 1e9
            idle = subtract(idle, spans)
    left = total(idle)
    if left:
        out["other"] = left / 1e9
    return out
