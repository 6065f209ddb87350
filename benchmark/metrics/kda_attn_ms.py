"""L3 round step: device time of the delta-rule mixer (scope ``fed_kda_attn``:
from the projections' outputs to ``W_o``'s operand: the short convolutions,
SiLU, the L2 norms, beta, the decay, the chunked core and the gated output
norm; forward, recomputation and backward), per round of the traced window."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_kda_attn",))
