"""L0 start-up: the program's phases ``model`` + ``fed`` + ``planes``
(spans ``fed_setup_*``): the model and its losses and initial weights,
``FedModel`` + ``FedOptimizer`` (layout, sketch tables, server state, kernel
self-checks), the planes. The programs built inside them are in these
seconds and in ``program_trace_s`` / ``program_load_s`` both."""

import _lifecycle


def read(ctx):
    return _lifecycle.phase_seconds(ctx, "model", "fed", "planes")
