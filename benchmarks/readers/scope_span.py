"""Device milliseconds per step of everything traced under one of
``scopes`` at ANY depth: an operation counts when the scope's
components appear, in order and side by side, anywhere in its framework
name — so a scope that encloses the library's own (``apex_loop/exit``
around ``apex_layernorm``, ``apex_linear`` and ``apex_xentropy``) reads
all the work it encloses, where ``scope_time`` gives each operation to
its innermost scope alone.  Own times, the lowest-numbered chip, the
steady window, forward and backward alike.

A program without the scope gives nothing."""

from benchmarks import programtrace


def _components(op_name):
    name = op_name.rpartition(":")[0] or op_name
    return programtrace._WRAPPERS.sub("", name).split("/")


def _holds(parts, want):
    n = len(want)
    return any(parts[i:i + n] == want for i in range(len(parts) - n + 1))


def read(ctx, scopes):
    pt = programtrace.load(ctx)
    if pt is None or ctx.steady is None or not pt.ops:
        return None
    wants = [scope.split("/") for scope in scopes]
    ops = [(n, max(s, ctx.steady.start), min(e, ctx.steady.end), p)
           for n, s, e, p in pt.ops[min(pt.ops)]
           if e > ctx.steady.start and s < ctx.steady.end]
    found = [own for op, own in programtrace.self_times(ops)
             if any(_holds(_components(op[0]), w) for w in wants)]
    if not found:
        return None
    return sum(found) / 1e6 / ctx.steady.steps
