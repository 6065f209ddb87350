"""L1 input pipeline: the program's phase ``data`` (span
``fed_setup_data``): the tokenizer, the datasets' preparation and the
loaders."""

import _lifecycle


def read(ctx):
    return _lifecycle.phase_seconds(ctx, "data")
