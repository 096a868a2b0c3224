"""A number the job read from its own program's outputs (a count the
program returns as an array, fetched when the job drains, never inside
a step), by name.  A job without it gives nothing."""


def read(ctx, count):
    return ctx.counts.get(count)
