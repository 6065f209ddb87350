"""L0 start-up: seconds jax spent tracing the programs built before the
window and lowering them to MLIR (``trace_s`` + ``lower_s`` of the
``program`` records): the host work a warm compile cache does not save."""

import _lifecycle


def read(ctx):
    return _lifecycle.build_seconds(ctx, "trace_s", "lower_s")
