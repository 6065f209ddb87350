"""Validation: host clock around the set-up's validation pass, the device
waited for at its end."""


def read(ctx):
    return ctx["val_ms"]
