"""Device milliseconds per step under ``apex_*`` scopes of the step
programs (``jax.named_scope`` inside the library, PR 25): the own time
of the operations of the lowest-numbered chip, inside the steady
window, whose framework name lies under one of ``scopes``
(``programtrace.scope_path``: forward and backward resolve alike).

With ``unscoped_share_of`` (roles of the job's programs) instead: the
percent of those programs' operation time that lies under NO scope —
what no layer metric owns.

A program without scopes (any commit before PR 25) gives nothing."""

from benchmarks import programtrace


def read(ctx, scopes=None, unscoped_share_of=None):
    pt = programtrace.load(ctx)
    if pt is None:
        return None
    ops = programtrace.scoped_ops(ctx, pt)
    if not any(path for path, _, _ in ops):
        return None
    if unscoped_share_of is not None:
        names = {ctx.programs[role] for role in unscoped_share_of}
        mine = [(path, own) for path, program, own in ops
                if program in names]
        whole = sum(own for _, own in mine)
        if whole <= 0:
            return None
        return sum(own for path, own in mine if path is None) / whole * 100.0
    found = [own for path, _, own in ops if programtrace.under(path, scopes)]
    if not found:
        return None
    return sum(found) / 1e6 / ctx.steady.steps
