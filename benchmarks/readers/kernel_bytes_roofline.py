"""A kernel family's share of its memory roofline, in percent: the
bytes its work has to move per step (counted from shapes, by the job)
over the chip's published bandwidth, divided by the summed device time
per step of the operations whose names start with ``prefix`` — every
run of them, a rematerialised forward included, so recomputation
lowers the share."""

from benchmarks import traceread


def read(ctx, prefix, count):
    if ctx.trace is None or ctx.steady is None or ctx.peaks is None \
            or count not in ctx.counts:
        return None
    s = traceread.kernel_seconds(ctx.trace, ctx.steady, prefix)
    if s <= 0:
        return None
    per_step = s / ctx.steady.steps
    return ctx.counts[count] / ctx.peaks["hbm_bytes_per_s"] / per_step * 100.0
