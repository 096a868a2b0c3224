"""nvtx-shaped annotation API over jax.named_scope (reference:
apex/pyprof/nvtx/nvmarker.py).

range_push/range_pop manage a stack of named_scope context managers;
`range` is the decorator/context form; `profile` wraps
jax.profiler.trace for XProf capture.  A scope opened while a function
is being TRACED names the operations traced inside it (their
``op_name``, which a TPU trace carries per device op); opened in eager
host code it names nothing in a profile — host regions are
``telemetry.span``'s job.

The push/pop stack is THREAD-LOCAL: a prefetch thread annotating its
own work must never pop a scope the main thread pushed (the reference
nvtx API is per-thread for the same reason).  ``range_pop`` is also
best-effort on teardown — a scope body that raised can leave
``jax.named_scope``'s own context in a state where ``__exit__``
raises, and an unwinding caller (an except-branch cleanup) must
still get its stack balanced rather than a second exception.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import List

import jax

_tls = threading.local()


def _stack() -> List:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def range_push(msg: str) -> int:
    cm = jax.named_scope(msg)
    cm.__enter__()
    stack = _stack()
    stack.append(cm)
    return len(stack)


def range_pop() -> int:
    stack = _stack()
    if not stack:
        return 0
    cm = stack.pop()
    try:
        cm.__exit__(None, None, None)
    except Exception:
        # best-effort unwind: the scope bookkeeping may already be
        # torn (a raising scope body, interpreter shutdown); the
        # caller's stack must still balance
        pass
    return len(stack)


@contextlib.contextmanager
def range(msg: str):
    with jax.named_scope(msg):
        yield


def annotate(msg: str = None):
    """Decorator: wrap a function in a named scope (nvmarker's wrapped
    torch-function behavior, opt-in per function here)."""
    def deco(fn):
        name = msg or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with jax.named_scope(name):
                return fn(*a, **kw)
        return wrapper
    return deco


@contextlib.contextmanager
def profile(logdir: str):
    """Capture an XProf trace of the enclosed region (TensorBoard-viewable
    — the DLProf story, natively)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
