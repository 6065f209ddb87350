"""L3 round step: device time of the recurrent stack's block applications
(inner scope ``fed_loop_body``, models/ouro.py: the L blocks of a pass, R
passes on the same weights; the attention's scopes and its two Mosaic calls
are nested in it; forward, recomputation and backward), per round of the
traced window. A Mosaic call that lost its scope path counts by its
kernel's name, in a program that names the scope at all."""

import _inner_scopes
import gqa_attn_ms

SCOPE = "fed_loop_body"


def seconds(ctx) -> float:
    """Seconds of the window under the scope; 0.0 where no operation
    carries it (a program without a recurrent stack)."""
    if not _inner_scopes.seconds(ctx, (SCOPE,)):
        return 0.0
    return _inner_scopes.seconds(ctx, (SCOPE,) + gqa_attn_ms.KERNELS)


def read(ctx):
    s = seconds(ctx) if ctx["rounds"] else 0.0
    return s / ctx["rounds"] * 1e3 if s else None
