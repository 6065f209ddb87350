"""L3 round step: device time of the download accounting (the changed-since
counts and the ``last_changed`` fold: the ``fed_accounting`` scope), per round
of the traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_stages(ctx, ("fed_accounting",))
