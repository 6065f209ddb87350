"""L0 start-up: programs built before the window that the persistent
cache did not hold and that took the backend 0.1 s or more (``program``
records with ``cache`` other than ``hit``): 0 on a warm machine."""

import _lifecycle


def read(ctx):
    progs = _lifecycle.programs_before_window(ctx)
    if progs is None:
        return None
    return float(sum(1 for p in progs if p.get("name") != "other"
                     and p.get("cache") != "hit"
                     and p.get("backend_s", 0.0) >= 0.1))
