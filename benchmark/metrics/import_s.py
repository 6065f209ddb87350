"""L0 start-up: the program's phase ``import`` (span ``fed_setup_import``):
process start, read from the OS, to the devices announced: the interpreter,
the imports, the harness's own set-up before the entry point, the backend."""

import _lifecycle


def read(ctx):
    return _lifecycle.phase_seconds(ctx, "import")
