"""Device: ``bytes_in_use`` at the last drain with no round in flight:
what the run holds between rounds (weights, server state, sketch tables,
accounting)."""

import _lifecycle


def read(ctx):
    return _lifecycle.gib(_lifecycle.drain_memory(ctx, at_rest=True),
                          "bytes_in_use")
