"""What the program itself names in the run's profiler trace: the stage of
every device operation (the outermost ``fed_*`` ``jax.named_scope`` in its
scope path) and the program's own host spans with their metadata.

``trace_reduce`` knows an operation by its HLO text and the spans of
``HOST_SPANS`` by name. The stage readers need the operation's *scope path*
(the HLO ``op_name``) and the spans the program opens inside itself
(``fed_window_wait``, ``fed_h2d``, ``fed_input_produce``, with ``round=`` in
their metadata), so this helper reads the same ``.xplane.pb`` a second time,
once for all the readers of a run. Against a program that names nothing (the
parent of the PR that brought the names) every reader finds nothing and
returns ``None``.

Where the scope path is: the profiler keeps what is the same for every
execution of one operation (its HLO text, and ``tf_op``: the HLO ``op_name``)
in the plane's *event metadata*, and ``jax.profiler.ProfileData`` shows only
an event's own stats (on a TPU: offset and duration). So the device planes
are read from the file's bytes, with the few lines of protobuf wire format
that takes (``xplane.proto``: XSpace > XPlane > XLine > XEvent, and the
XEventMetadata / XStatMetadata maps); host planes, whose millions of events
are not wanted, are skipped whole and their spans read through
``ProfileData``.

Like ``trace_reduce``: one pass makes plain lists, everything else is
arithmetic on them, and the tests run that arithmetic on a small recorded
list (tests/data/program_trace_small.json).
"""

from __future__ import annotations

import os
import re
import sys
from dataclasses import dataclass, field

# the program's device stages (commefficient_tpu/profiling.DEVICE_STAGES; a
# copy, because the benchmark also runs against programs that lack the list)
STAGES = ("fed_client_grad", "fed_client_compress", "fed_server_estimate",
          "fed_server_topk", "fed_server_resketch", "fed_server_apply",
          "fed_telemetry_metrics", "fed_accounting", "fed_val")
# the program's host spans that trace_reduce.HOST_SPANS does not load
SPANS = ("fed_window_wait", "fed_h2d", "fed_input_wait", "fed_input_produce",
         "fed_telemetry_host")
# the metadata stats read: the operation's scope path (on the v5e with jax
# 0.9: "jit(client_step)/fed_client_grad/...") and the program it belongs to
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"
_COMPONENT = re.compile(r"fed_[a-z_]+")


@dataclass
class ProgramTrace:
    ops: dict = field(default_factory=dict)     # chip -> [(stage|None, s, e)]
    spans: dict = field(default_factory=dict)   # name -> [(s, e, {meta})]


def stage_of(path: str):
    """The outermost stage in a scope path, or None. A component may wrap the
    scope in the transform that made the operation
    (``transpose(jvp(fed_client_grad))``, ``vmap(fed_client_grad)``), and a
    kernel's own name (``fed_sketch_vec``) is no stage: match the component
    against the known stages, outermost first."""
    for component in path.split("/"):
        for name in _COMPONENT.findall(component):
            if name in STAGES:
                return name
    return None


def stages_of(ops):
    """``[(stage|None, start, end)]`` from ``[(scope path, program, start,
    end)]`` in start order. An operation with no scope path at all is one
    the compiler made (a copy, a layout change, the packed mask of a
    select-and-scatter): it takes the stage of the last staged operation
    before it in the same program. One with a path that holds no stage
    (an argument's copy, ``jit(_threefry_split)``) stays unscoped."""
    out, last = [], {}
    for path, program, s, e in sorted(ops, key=lambda o: o[2]):
        stage = stage_of(path)
        if stage is not None:
            last[program] = stage
        elif not path:
            stage = last.get(program)
        out.append((stage, s, e))
    return out


# ---- the few lines of protobuf wire format --------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` pair of offsets for a length-delimited field; fixed
    32/64-bit fields are passed over."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"wire type {kind} at {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, val = 0, None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            val = v
    return key, val


def _device_plane(buf, span, ops_line):
    """``[(scope path, program, start_ns, end_ns)]`` of a device plane's
    operations line. XPlane: lines=3, event_metadata=4, stat_metadata=5;
    XLine: name=2, timestamp_ns=3, events=4; XEvent: metadata_id=1,
    offset_ps=2, duration_ps=3; XEventMetadata: stats=5; XStat:
    metadata_id=1, uint64_value=3, int64_value=4, str_value=5, ref_value=7
    (a string kept as a stat name)."""
    lines, event_meta, stat_names = [], {}, {}
    for no, v in _fields(buf, *span):
        if no == 3:
            lines.append(v)
        elif no == 4:
            key, val = _map_entry(buf, v)
            if val is not None:
                event_meta[key] = val
        elif no == 5:
            key, val = _map_entry(buf, v)
            if val is not None:
                stat_names[key] = next(
                    (_text(buf, x) for n, x in _fields(buf, *val) if n == 2),
                    "")
    known = {}

    def path_and_program(meta_id):
        if meta_id not in known:
            stats = {}
            for no, v in _fields(buf, *event_meta.get(meta_id, (0, 0))):
                if no != 5:
                    continue
                name, val = None, None
                for n, x in _fields(buf, *v):
                    if n == 1:
                        name = stat_names.get(x)
                    elif n in (3, 4):
                        val = x
                    elif n == 5:
                        val = _text(buf, x)
                    elif n == 7:
                        val = stat_names.get(x)
                if name in (PATH_STAT, PROGRAM_STAT):
                    stats[name] = val
            known[meta_id] = (stats.get(PATH_STAT) or "",
                              stats.get(PROGRAM_STAT))
        return known[meta_id]

    out = []
    for line in lines:
        name, t0_ns, events = "", 0, []
        for no, v in _fields(buf, *line):
            if no == 2:
                name = _text(buf, v)
            elif no == 3:
                t0_ns = v
            elif no == 4:
                events.append(v)
        if name != ops_line:
            continue
        for ev in events:
            meta_id = off_ps = dur_ps = 0
            for no, v in _fields(buf, *ev):
                if no == 1:
                    meta_id = v
                elif no == 2:
                    off_ps = v
                elif no == 3:
                    dur_ps = v
            start = t0_ns + off_ps / 1000.0
            out.append(path_and_program(meta_id)
                       + (start, start + dur_ps / 1000.0))
    return out


def device_paths(path: str, tr) -> dict:
    """chip -> ``[(scope path, program, start_ns, end_ns)]`` from the file's
    bytes."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for no, plane in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, *plane)
                     if n == 2), "")
        if name.startswith(tr.DEVICE_PLANE):
            out[name] = _device_plane(buf, plane, tr.OPS_LINE)
    return out


def load(trace_dir: str, tr) -> ProgramTrace:
    """The run's ``.xplane.pb``, once (``tr`` is ``trace_reduce``, for the
    plane and line names it already fixes)."""
    import jax

    path = tr.find_xplane(trace_dir)
    out = ProgramTrace()
    for chip, ops in device_paths(path, tr).items():
        out.ops[chip] = stages_of(ops)
    wanted = set(SPANS)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in wanted:
                    out.spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         {k: str(v) for k, v in ev.stats}))
    for spans in out.spans.values():
        spans.sort(key=lambda s: s[0])
    return out


_LOADED: dict = {}


def of(ctx):
    """The run's ``ProgramTrace``, read once however many readers ask; None
    where the run left no trace to read."""
    trace_dir = os.path.join(os.environ.get("COMMEFFICIENT_RUN_DIR", ""),
                             "trace")
    if trace_dir not in _LOADED:
        try:
            _LOADED[trace_dir] = load(trace_dir, ctx["tr"])
        except (OSError, ValueError, IndexError) as e:
            # no trace, or bytes this reader cannot follow: the readers
            # report nothing, the run's other metrics stand
            print(f"bench: program trace not read: {e!r}", file=sys.stderr)
            _LOADED[trace_dir] = None
    return _LOADED[trace_dir]


# ---- arithmetic on the lists ----------------------------------------------

def stage_seconds(pt: ProgramTrace, stages, lo, hi, tr) -> float:
    """Seconds in which an operation of one of ``stages`` ran (union of their
    intervals inside [lo, hi]), averaged over the chips traced. ``stages``
    may hold None: the operations under no stage."""
    stages = set(stages)
    if not pt.ops:
        return 0.0
    per_chip = [tr.total(tr.union(tr.clip(
        [(s, e) for st, s, e in ops if st in stages], lo, hi)))
        for ops in pt.ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def names_stages(pt: ProgramTrace, lo, hi) -> bool:
    """Whether any operation of the window carries a stage at all."""
    return any(st is not None and e > lo and s < hi
               for ops in pt.ops.values() for st, s, e in ops)


def span_seconds(pt: ProgramTrace, name, lo, hi, tr) -> float:
    """Seconds inside the program's ``name`` spans, clipped to [lo, hi].
    Spans of one name on one thread do not overlap; the sum is the time."""
    return tr.total(tr.clip([(s, e) for s, e, _ in pt.spans.get(name, ())],
                            lo, hi)) / 1e9


def span_rounds(pt: ProgramTrace, name, lo, hi):
    """The ``round`` metadata of the ``name`` spans that start in [lo, hi]."""
    return [int(meta["round"]) for s, _, meta in pt.spans.get(name, ())
            if lo <= s <= hi and "round" in meta]


# ---- what the readers share -------------------------------------------------

def read_stages(ctx, stages):
    """ms a round of device time under ``stages``; None where the trace
    names none of them."""
    pt = of(ctx)
    if pt is None or not ctx["rounds"]:
        return None
    s = stage_seconds(pt, stages, ctx["lo"], ctx["hi"], ctx["tr"])
    return s / ctx["rounds"] * 1e3 if s else None


def read_span(ctx, name):
    """ms a round inside the program's ``name`` spans; None without any."""
    pt = of(ctx)
    if pt is None or not ctx["rounds"]:
        return None
    s = span_seconds(pt, name, ctx["lo"], ctx["hi"], ctx["tr"])
    return s / ctx["rounds"] * 1e3 if s else None
