"""RF megabytes served per second: every acquisition the window served,
times its input bytes, over the window (host clock, from the window's
opening to the last image landing on the host)."""


def read(ctx):
    return ctx.served_bytes / ctx.window_s / 1e6
