"""L3 round step: device time of the latent attention's core (scope
``fed_mla_attn``: RoPE, scores, causal softmax, values; forward, recomputation
and backward; the low-rank projections around it are not in it), per round of
the traced window."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_mla_attn",))
