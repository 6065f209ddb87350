"""L3 round step: device time of the expert layers' routing (scope
``fed_moe_route``: router scores and top-k, grouping the held pairs by expert,
gathering their rows and adding the gated results back; forward,
recomputation and backward), per round of the traced window."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_moe_route",))
