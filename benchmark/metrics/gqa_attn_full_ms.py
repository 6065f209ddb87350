"""L3 round step: the part of ``gqa_attn_ms`` spent in the full-attention
layers (scope ``fed_gqa_attn_full``, nested in ``fed_gqa_attn``: 48 query
heads, every key up to the diagonal), per round of the traced window; the
window layers (``fed_gqa_attn_window``) are the difference."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_gqa_attn_full",))
