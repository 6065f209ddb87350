"""L4 kernels: device time of the whole compression path, XLA operations
included: client-side flatten and sketch accumulate, estimates, top-k
threshold and mask, re-sketch (the ``fed_client_compress``,
``fed_server_estimate``, ``fed_server_topk`` and ``fed_server_resketch``
scopes), per round of the traced window. ``sketch_kernel_ms`` is the Mosaic
calls alone."""

import _program_trace

STAGES = ("fed_client_compress", "fed_server_estimate", "fed_server_topk",
          "fed_server_resketch")


def read(ctx):
    return _program_trace.read_stages(ctx, STAGES)
