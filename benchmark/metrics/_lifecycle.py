"""What the program records of its own start-up and device memory
(``commefficient_tpu/profiling.py``: ``phase`` / ``PHASES``, the program
listener, ``memory_sample``), read from the run's event log: the ``setup``
event's phases, the ``program`` records (one a build of a named program, the
small ones summed under ``other``), and the memory samples of the ``drain``
events.

"Before the window" is before the dispatch of the first of the run's last
``ctx['rounds']`` rounds: everything the set-up built, warm-up call and
validation pass included. Against a program that writes none of these events
(any before PR 36) every reader finds nothing and returns ``None``.
"""

import json
import os

_EVENTS: dict = {}
_KINDS = ('"setup"', '"program"', '"drain"', '"round"')


def events(ctx=None):
    """``{kind: [records]}`` of the run's log, read once; {} without one."""
    path = os.path.join(os.environ.get("COMMEFFICIENT_RUN_DIR", ""),
                        "telemetry.jsonl")
    if path not in _EVENTS:
        out = {}
        try:
            with open(path) as f:
                for line in f:
                    if not any(k in line[:40] for k in _KINDS):
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        break           # a torn last line
                    out.setdefault(rec.get("ev"), []).append(rec)
        except OSError:
            pass
        _EVENTS[path] = out
    return _EVENTS[path]


def phase_seconds(ctx, *names):
    """Seconds of the set-up phases called ``names``; None where the log has
    no ``setup`` event or none of them (``import`` where the OS gave no
    process start)."""
    setups = events(ctx).get("setup")
    if not setups:
        return None
    found = [p["seconds"] for p in setups[-1].get("phases", ())
             if p.get("phase") in names]
    return float(sum(found)) if found else None


def window_start(ctx):
    """Wall time at which the dispatch of the window's first round began
    (``t_dispatch`` is stamped when the dispatch has returned); None
    without round records."""
    rounds = events(ctx).get("round", ())
    n = int(ctx.get("rounds") or 0)
    if not n or len(rounds) < n or "t_dispatch" not in rounds[-n]:
        return None
    first = rounds[-n]
    return first["t_dispatch"] - first.get("dispatch_ms", 0.0) / 1e3


def programs_before_window(ctx):
    """The ``program`` records of set-up; None where the log has none."""
    progs = events(ctx).get("program")
    t0 = window_start(ctx)
    if not progs or t0 is None:
        return None
    return [p for p in progs if p.get("t", 0.0) < t0]


def build_seconds(ctx, *keys):
    progs = programs_before_window(ctx)
    if progs is None:
        return None
    return float(sum(p.get(k, 0.0) for p in progs for k in keys))


def drain_memory(ctx, at_rest=False):
    """The memory sample of the window's last drain (``at_rest``: of the
    last drain with nothing in flight); None where the log or the backend
    gives none."""
    for rec in reversed(events(ctx).get("drain", ())):
        mem = rec.get("memory")
        if mem and (not at_rest or rec.get("inflight") == 0):
            return mem
    return None


def gib(mem, key):
    return mem[key] / 2**30 if mem and key in mem else None
