"""L0 start-up: seconds jax spent compiling, or fetching compiled programs
from the persistent cache, during set-up (jax.monitoring durations)."""


def read(ctx):
    return ctx["compile_s"] or None
