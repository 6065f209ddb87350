"""L2 round engine: host time inside the engine's window wait (the program's
``fed_window_wait`` span around ``jax.block_until_ready`` in
``PipelinedRoundEngine.submit``), per round of the traced window: the one
place where the host waits for the device."""

import _program_trace


def read(ctx):
    return _program_trace.read_span(ctx, "fed_window_wait")
