"""L0 start-up: seconds in the backend for the programs built before the
window (``backend_s`` of the ``program`` records): a compile, or the
persistent cache's load, each counted once (``compile_s`` adds the load to
the span that already contains it)."""

import _lifecycle


def read(ctx):
    return _lifecycle.build_seconds(ctx, "backend_s")
