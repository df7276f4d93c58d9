"""Milliseconds of device time a call of the module(s)."""

from . import module_time


def read(ctx, p):
    n, s = module_time(ctx, p["modules"])
    return 1e3 * s / n if n else None
