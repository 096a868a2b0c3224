"""Percent of the traced steady window in which no operation ran on
the device (1 - union of device-op intervals, averaged over chips)."""

from benchmarks import traceread


def read(ctx):
    if ctx.trace is None or ctx.steady is None:
        return None
    busy = traceread.busy_seconds(ctx.trace, ctx.steady)
    if busy <= 0:
        return None
    return (1.0 - busy / ctx.steady.seconds) * 100.0
