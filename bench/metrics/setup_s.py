"""Seconds from process start to the window's opening: imports, device
start-up, RF, the program's constants and its compiled programs."""


def read(ctx):
    return ctx.setup_s
