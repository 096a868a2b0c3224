"""A program's share of its memory roofline, in percent: the bytes its
work has to move (``counts.py``, by the job) over the chip's published
bandwidth, divided by the program's device time per step."""

from benchmarks.readers import module_time


def read(ctx, program, count):
    s = module_time.seconds_per_step(ctx, program)
    if s is None or ctx.peaks is None:
        return None
    return ctx.counts[count] / ctx.peaks["hbm_bytes_per_s"] / s * 100.0
