"""Trace-time metric tape: how producers deep in the stack report.

The telemetry invariant is ZERO additional host syncs per step, which
rules out the obvious wiring (every producer calling back to a host
object with a concrete value).  Instead, producers call
:func:`emit` with the *traced* scalar they already computed —
``FlatGradPipeline`` with the global grad norm, the LAMB trust-factor
helper with the per-bucket max trust ratio, the bucketed reducer with
its payload size — and an active :class:`Tape` (pushed by
``telemetry.instrument`` around the user's train step while it is
being traced) collects them.  At the end of the step body the
instrument wrapper writes the collected values into the
:class:`~apex_tpu.telemetry.ring.MetricRing` with static
``dynamic_update_slice`` writes: the metrics ride the step's own jit,
and the host never sees a value until the window flush.

With no tape active, :func:`emit` is a single truthiness check on a
module list — producers pay nothing when telemetry is off, and the
calls are trace-time Python, so they are not even present in the
compiled program.

Safety rule: a tape only captures a TRACED value when it was emitted
under the same trace the tape was pushed in.  A tracer from any other
trace — a producer's internal jit under an eager tape (the stateful
``optimizer.step`` facade), a separately-jitted helper inside an
instrumented step, a nested transform — would escape its trace if
captured, so it is silently dropped instead: the metric is absent for
that step, never a crash.  Concrete values (host floats, committed
arrays) are safe from anywhere and always land.
"""

from __future__ import annotations

import threading
from typing import Dict, List

import jax
import jax.numpy as jnp

# combine rules for a metric emitted more than once in one step (e.g.
# one emission per bucket): "last" overwrites, "max"/"sum" fold
# elementwise, "rss" root-sum-squares (the right combine for norms)
_REDUCES = ("last", "max", "sum", "rss")


class Tape:
    """One step's collected metrics (name -> traced f32 scalar)."""

    __slots__ = ("values", "trace")

    def __init__(self):
        self.values: Dict[str, jax.Array] = {}
        # the trace this tape belongs to: only tracers of THIS trace
        # may be captured (anything else would escape its trace when
        # the instrument wrapper writes the ring)
        self.trace = jax.core.trace_ctx.trace


# THREAD-LOCAL, like pyprof.nvtx's range stack and for the same
# reason: a background thread (data prefetcher, async checkpoint
# writer) running producer code must never land its values on the
# main thread's step tape
_tls = threading.local()


def _stack() -> List[Tape]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def push() -> Tape:
    t = Tape()
    _stack().append(t)
    return t


def pop() -> Tape:
    return _stack().pop()


def active() -> bool:
    return bool(_stack())


def emit(name: str, value, reduce: str = "last") -> None:
    """Report a scalar metric to the active tape (no-op without one).

    ``value`` may be a traced or concrete scalar; it is recorded as
    f32.  ``reduce`` folds repeated emissions of the same name within
    one step (per-bucket producers): "last" | "max" | "sum" | "rss".
    """
    if reduce not in _REDUCES:
        # validated BEFORE the no-tape early return: a producer's typo
        # must fail in untelemetered runs too, not lie latent until
        # the first instrumented step
        raise ValueError(f"unknown reduce {reduce!r}; one of {_REDUCES}")
    stack = _stack()
    if not stack:
        return
    tape = stack[-1]
    if (isinstance(value, jax.core.Tracer)
            and jax.core.trace_ctx.trace is not tape.trace):
        # foreign trace (nested jit / transform): capturing would leak
        # the tracer (module docstring)
        return
    v = jnp.asarray(value, jnp.float32)
    old = tape.values.get(name)
    if old is None or reduce == "last":
        tape.values[name] = v
    elif reduce == "max":
        tape.values[name] = jnp.maximum(old, v)
    elif reduce == "sum":
        tape.values[name] = old + v
    else:  # rss
        tape.values[name] = jnp.sqrt(old * old + v * v)
