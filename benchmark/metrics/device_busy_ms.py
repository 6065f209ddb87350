"""L3 round step: union of the device-op intervals of the traced window,
per round of that window."""


def read(ctx):
    if not ctx["busy_s"] or not ctx["rounds"]:
        return None
    return ctx["busy_s"] / ctx["rounds"] * 1e3
