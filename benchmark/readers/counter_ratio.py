"""100 * part / (part + rest) of two counters' gains in the window."""

from . import series


def read(ctx, p):
    part = sum(series(ctx["counters"], p["part"]).values())
    rest = sum(series(ctx["counters"], p["rest"]).values())
    return 100.0 * part / (part + rest) if part + rest > 0 else None
