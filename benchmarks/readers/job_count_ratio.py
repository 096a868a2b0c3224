"""A table the job read from its own program's outputs (rows of counts,
fetched when the job drains, never inside a step) against what was
expected of it: each row's sum over ``len(row) * counts[per]``, the
mean over rows; the largest row's goes to standard error.  With
``expert_tokens`` (layers, held) over ``expected_tokens_per_expert``:
the rows routed to this chip's experts a layer over what an unbiased
router would send them, 1.0 a fill of held / all experts.

It is the NEWEST step's table, so the window's end, where the device
metrics it is read beside are from the traced run's early steps.  A
run with no steady device window to read it beside (a CPU rehearsal,
whose metric set ``tests/benchmark/test_keye_cell.py`` pins) and a job
without either count give nothing."""

import sys


def read(ctx, count, per):
    rows, expected = ctx.counts.get(count), ctx.counts.get(per)
    if ctx.steady is None or not rows or not expected:
        return None
    ratios = [sum(row) / (len(row) * expected) for row in rows]
    print(f"{count} over {per}: largest row {max(ratios):.6g} of "
          f"{len(ratios)}", file=sys.stderr, flush=True)
    return sum(ratios) / len(ratios)
