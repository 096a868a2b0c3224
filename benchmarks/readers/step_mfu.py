"""The whole step's share of the chips' peak, in percent: model FLOPs
per step from shapes (``counts.py``, no recomputation) over the traced
steady window's time per step, the chips of the cell and the published
peak."""


def read(ctx):
    if ctx.steady is None or ctx.peaks is None:
        return None
    per_step = ctx.steady.seconds / ctx.steady.steps
    return (ctx.counts["step_flops"]
            / (per_step * ctx.chips * ctx.peaks["flops_per_s"]) * 100.0)
