"""Seconds inside the named span(s), summed over threads, as a share of
the window."""


def read(ctx, p):
    spans = [ctx["spans"][s] for s in p["spans"] if s in ctx["spans"]]
    if not spans:
        return None
    return 100.0 * sum(spans) / ctx["window_s"]
