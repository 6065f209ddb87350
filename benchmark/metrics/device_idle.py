"""Device: share of the traced window in which no operation ran on the chip
(1 - busy union / window), in percent."""


def read(ctx):
    if not ctx["busy_s"]:
        return None
    return (1.0 - ctx["busy_s"] / ctx["window_s"]) * 100.0
