"""L3 round step: device time of the clients' forward, loss and backward (the
operations under the program's ``fed_client_grad`` scope, whatever transform
wrapped them), per round of the traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_stages(ctx, ("fed_client_grad",))
