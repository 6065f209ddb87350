"""L2 round engine: time inside the program's ``fed_drain`` spans (the
batched metric fetch), per round of the traced window."""


def read(ctx):
    tr = ctx["tr"]
    s = tr.span_seconds(ctx["trace"], "fed_drain", ctx["lo"], ctx["hi"])
    return s / ctx["rounds"] * 1e3 if s and ctx["rounds"] else None
