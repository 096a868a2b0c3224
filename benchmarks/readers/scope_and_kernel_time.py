"""Device milliseconds per step of everything traced under one of
``scopes`` at any depth (``scope_span``) PLUS the operations whose names
start with one of ``prefixes`` (``traceread.kernel_seconds``): for work
part of which the compiler names itself.  ``lax.ragged_dot`` becomes
the compiler's ``ragged-dot-*`` kernels, whose events carry that name
in place of the framework's, so the scope they were traced under does
not reach them.  An operation is counted once as long as no prefix
names an operation that also carries one of the scopes.

A program with neither gives nothing."""

from benchmarks import traceread
from benchmarks.readers import scope_span


def read(ctx, scopes, prefixes):
    if ctx.trace is None or ctx.steady is None:
        return None
    under = scope_span.read(ctx, scopes)
    named = sum(traceread.kernel_seconds(ctx.trace, ctx.steady, p)
                for p in prefixes) / ctx.steady.steps * 1e3
    if under is None and named <= 0:
        return None
    return (under or 0.0) + named
