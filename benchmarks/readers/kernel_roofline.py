"""A kernel family's share of its compute roofline, in percent: the
FLOPs its work needs per step (``counts.py``, by the job) over the
chip's published peak, divided by the summed device time per step of
the operations whose names start with ``prefix``."""

from benchmarks import traceread


def read(ctx, prefix, count):
    if ctx.trace is None or ctx.steady is None or ctx.peaks is None \
            or count not in ctx.counts:
        return None
    s = traceread.kernel_seconds(ctx.trace, ctx.steady, prefix)
    if s <= 0:
        return None
    per_step = s / ctx.steady.steps
    return ctx.counts[count] / ctx.peaks["flops_per_s"] / per_step * 100.0
