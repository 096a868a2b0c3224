"""A count the harness took over the window, by name."""


def read(ctx, counter):
    return ctx.counters.get(counter)
