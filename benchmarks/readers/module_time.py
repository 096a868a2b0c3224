"""Device milliseconds per step of one of the job's programs: the
summed durations of its module's executions on the lowest-numbered
chip, inside the steady window, over the window's steps."""

from benchmarks import traceread


def seconds_per_step(ctx, program):
    if ctx.trace is None or ctx.steady is None:
        return None
    events = traceread.clipped(traceread.module_events(
        ctx.trace, min(ctx.trace.devices), ctx.programs[program]),
        ctx.steady)
    if not events:
        return None
    return sum(e - s for _, s, e in events) / 1e9 / ctx.steady.steps


def read(ctx, program):
    s = seconds_per_step(ctx, program)
    return None if s is None else s * 1e3
