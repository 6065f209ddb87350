"""L2 round engine: host time of the batch's host-to-device conversion (the
program's ``fed_h2d`` span in ``FedModel.begin_round``), per round of the
traced window."""

import _program_trace


def read(ctx):
    return _program_trace.read_span(ctx, "fed_h2d")
