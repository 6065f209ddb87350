"""L3 round step: device time of the grouped-query attention's core (scope
``fed_gqa_attn``: RoPE on q and k, the two Mosaic calls ``fed_gqa_attn_fwd``
/ ``fed_gqa_attn_bwd`` or the ``einsum`` path's products and softmax, the
head gate; forward, recomputation and backward; the projections around it
are not in it), per round of the traced window. A Mosaic call that lost its
scope path counts by its kernel's name."""

import _inner_scopes

KERNELS = ("fed_gqa_attn_fwd", "fed_gqa_attn_bwd")


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_gqa_attn",) + KERNELS)
