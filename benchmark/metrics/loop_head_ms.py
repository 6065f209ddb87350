"""L3 round step: device time of what a recurrent stack reads after every
pass (inner scope ``fed_loop_head``, models/ouro.py: the final norm, the
head over the whole vocabulary, the next-token NLL and the exit gate, once a
pass; forward, recomputation and backward), per round of the traced
window."""

import _inner_scopes


def read(ctx):
    return _inner_scopes.read_ms(ctx, ("fed_loop_head",))
