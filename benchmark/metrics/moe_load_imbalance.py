"""L3 round step: the largest held expert's load over the mean held load
(1 = balanced, the count of held experts = everything on one), the program's
own counter ``moe_load_max_over_mean``, mean over the window's rounds."""

import _inner_scopes


def read(ctx):
    vals = [r["moe_load_max_over_mean"]
            for r in _inner_scopes.round_counters(ctx)
            if "moe_load_max_over_mean" in r]
    return sum(vals) / len(vals) if vals else None
