"""L3 round step: device time of the routed experts' grouped products and
their activation (scope ``fed_moe_experts``, and the grouped products
themselves by the name XLA:TPU gives them, ``_inner_scopes.GROUPED_PRODUCT``:
forward, recomputation and backward), per round of the traced window. The
stage readers count those products under no stage (``unscoped_ms``)."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_moe_experts",),
                                 _inner_scopes.GROUPED_PRODUCT)
