"""A kernel family's share of its roofline, in percent: the least time
its work could take per step — the larger of its FLOPs (``counts.py``,
by the job) over the chip's published peak and its bytes over the
published bandwidth — divided by the summed device time per step of the
operations whose names start with ``prefix``."""

from benchmarks import traceread


def read(ctx, prefix, flops, bytes):
    if ctx.trace is None or ctx.steady is None or ctx.peaks is None \
            or flops not in ctx.counts or bytes not in ctx.counts:
        return None
    s = traceread.kernel_seconds(ctx.trace, ctx.steady, prefix)
    if s <= 0:
        return None
    least = max(ctx.counts[flops] / ctx.peaks["flops_per_s"],
                ctx.counts[bytes] / ctx.peaks["hbm_bytes_per_s"])
    return least / (s / ctx.steady.steps) * 100.0
