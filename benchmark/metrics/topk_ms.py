"""L4 kernels: device time of the top-k alone: threshold resolve (Pallas count
kernels, or the XLA descent above 32M coordinates) and the mask (the
``fed_server_topk`` scope), per round of the traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_stages(ctx, ("fed_server_topk",))
