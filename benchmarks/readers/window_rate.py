"""Units (real tokens, images) of all the window's steps over all its
time, all chips of the cell together."""


def read(ctx):
    return (ctx.units_per_step * ctx.steps / ctx.window_s
            if ctx.steps else None)
