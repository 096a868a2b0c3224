"""Programs launched per step: executions on the ``XLA Modules`` line
of the lowest-numbered chip that start inside the steady window, over
its steps (the step's real programs and every tiny one the loop and
the library launch beside them)."""

from benchmarks import traceread


def read(ctx):
    if ctx.trace is None or ctx.steady is None:
        return None
    modules = ctx.trace.devices[min(ctx.trace.devices)].get(
        traceread.MODULE_LINE, [])
    n = sum(1 for _, start, _ in modules
            if ctx.steady.start <= start < ctx.steady.end)
    return n / ctx.steady.steps if n else None
