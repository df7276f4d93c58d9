"""100 * part / (part + rest) of the window's gains of two series of one
counter, each named whole, labels and all (``name{label="value"}``, as the
program's registry keys it): the share of a counter's events that bore one
label. ``counter_ratio`` sums a counter whatever its labels and cannot tell
them apart. A series that counted nothing reads 0, so a program that has
the counter and never took the path reads a share of 0.0; where neither
series counted there is nothing to read."""


def read(ctx, p):
    part = ctx["counters"].get(p["part"], 0)
    rest = ctx["counters"].get(p["rest"], 0)
    return 100.0 * part / (part + rest) if part + rest > 0 else None
