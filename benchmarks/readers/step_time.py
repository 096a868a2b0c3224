"""Milliseconds per step: the window's time over its whole steps."""


def read(ctx):
    return ctx.window_s / ctx.steps * 1e3 if ctx.steps else None
