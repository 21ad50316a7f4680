"""Share of a steady slice of the window in which the device ran no
operation (profiler trace; averaged over the cell's chips)."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
