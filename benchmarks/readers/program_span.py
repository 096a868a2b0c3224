"""Host milliseconds per step inside the library's own spans
(``apex/<layer>/<what>``: ``telemetry.span``, on the profiler's clock),
clipped to the steady window and taken over its steps.

``spans`` with ``minus``: wall time covered by the spans named in
``spans`` (their union: a span inside another counts once) less the
part the spans named in ``minus`` cover — ``apex/optim/step`` less
``apex/optim/dispatch``, the one call that blocks on the device.

``idle_under`` (a name prefix) instead: the device-idle time whose
gap's midpoint lies inside a span of that prefix, the innermost
winning; the split by span goes to standard error.

A program without the spans (any commit before PR 25) gives nothing."""

import sys

from benchmarks import programtrace, traceread


def _intervals(events, names, window):
    return traceread.merge(
        (max(s, window.start), min(e, window.end))
        for name, s, e, _ in events if name in names)


def read(ctx, spans=None, minus=(), idle_under=None):
    pt = programtrace.load(ctx)
    if pt is None or ctx.steady is None:
        return None
    window = ctx.steady
    if idle_under is not None:
        return _idle(ctx, pt.spans(idle_under))
    covered = _intervals(pt.host, set(spans), window)
    if not covered:
        return None
    less = traceread.intersect(covered,
                               _intervals(pt.host, set(minus), window))
    return ((traceread.total(covered) - traceread.total(less))
            / 1e6 / window.steps)


def _idle(ctx, spans):
    window = ctx.steady
    if not spans:
        return None
    ops = ctx.trace.devices[min(ctx.trace.devices)].get(
        traceread.OP_LINE, [])
    busy = traceread.merge((s, e) for _, s, e in
                           traceread.clipped(ops, window))
    split = {}
    for s, e in traceread.gaps(busy, (window.start, window.end)):
        mid = (s + e) / 2
        cover = [h for h in spans if h[1] <= mid < h[2]]
        if cover:
            name = max(cover, key=lambda h: h[1])[0]
            split[name] = split.get(name, 0.0) + (e - s)
    print("device idle ms/step by the library's span:",
          {k: round(v / 1e6 / window.steps, 6)
           for k, v in sorted(split.items(), key=lambda kv: -kv[1])},
          file=sys.stderr, flush=True)
    return sum(split.values()) / 1e6 / window.steps
