"""A number the harness took itself: clocks, rates, idle share, peak HBM,
compiles in the window."""


def read(ctx, p):
    return ctx["values"].get(p["key"])
