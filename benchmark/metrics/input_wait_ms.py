"""L1 input pipeline: host time the loop spends inside the inner loader's
``next`` (the harness's wrapper clocks it), per round of the window."""


def read(ctx):
    return ctx["wait_s"] / ctx["rounds"] * 1e3 if ctx["rounds"] else None
