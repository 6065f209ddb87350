"""L1 input pipeline: time the prefetch thread spends assembling a batch (the
program's ``fed_input_produce`` span around one ``next`` of the inner loader,
on the producer thread), per round of the traced window. It overlaps the
device, so it may exceed ``input_wait_ms``: it says what the loader can
sustain, not what the loop waited."""

import _program_trace


def read(ctx):
    return _program_trace.read_span(ctx, "fed_input_produce")
