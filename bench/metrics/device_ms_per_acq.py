"""Device milliseconds per served acquisition: the trace's busy share of
the steady slice, times the window, over the acquisitions served."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.served:
        return None
    return t["busy_s"] / t["window_s"] * ctx.window_s / ctx.served * 1e3
