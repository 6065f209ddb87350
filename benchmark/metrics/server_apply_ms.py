"""L3 round step: device time of the server's state transition (momentum and
error sweep, ``ps_weights - update``, guard select, client-state scatters: the
``fed_server_apply`` scope), per round of the traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_stages(ctx, ("fed_server_apply",))
