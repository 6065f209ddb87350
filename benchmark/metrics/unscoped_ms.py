"""Device: device time under no ``fed_*`` scope, per round of the traced
window: what the stage metrics leave out of ``device_busy_ms``. None against a
program that names no stage at all (every operation would be unscoped)."""

import _program_trace


def read(ctx):
    pt = _program_trace.of(ctx)
    if pt is None or not ctx["rounds"] or not _program_trace.names_stages(
            pt, ctx["lo"], ctx["hi"]):
        return None
    s = _program_trace.stage_seconds(pt, (None,), ctx["lo"], ctx["hi"],
                                     ctx["tr"])
    return s / ctx["rounds"] * 1e3
