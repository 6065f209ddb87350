"""L2 round engine: self time of the program's ``fed_round`` step spans (LR
step, client-phase and server-phase dispatch), less any ``fed_drain`` inside
them, per round of the traced window."""


def read(ctx):
    tr = ctx["tr"]
    s = tr.self_seconds(ctx["trace"], "fed_round", ("fed_drain",),
                        ctx["lo"], ctx["hi"])
    return s / ctx["rounds"] * 1e3 if s and ctx["rounds"] else None
