"""Host milliseconds per step that the loop's thread spent ON the CPU
inside the named spans, over the traced steps: the host's own work per
step.  Wall time would not do: a dispatch blocks while the device still
uses a donated buffer, and that wait is the device's time."""


def read(ctx, spans):
    rows = [r for r in ctx.span_rows if r[0] in spans]
    steps = sum(1 for r in ctx.span_rows if r[0] == "dispatch_fwd_bwd")
    if not rows or not steps:
        return None
    return sum(cpu for _, _, cpu in rows) / steps * 1e3
