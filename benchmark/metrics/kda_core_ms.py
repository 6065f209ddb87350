"""L3 round step: device time of the delta rule's chunked core alone (scope
``fed_kda_core``, nested in ``fed_kda_attn``: the pair matrices, the
triangular solve, the scan over chunks; forward, recomputation and backward),
per round of the traced window."""

import _inner_scopes


def seconds(ctx) -> float:
    return _inner_scopes.seconds(ctx, ("fed_kda_core",)) if ctx["rounds"] \
        else 0.0


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_kda_core",))
