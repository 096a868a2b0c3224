"""Recompilation counter: the runtime companion to the APX30x rules.

apexlint's APX301-303 flag retrace *hazards* statically; this module
counts retraces that actually happen at run time.  Two hooks:

- ``jax.monitoring``: JAX stamps every trace /
  lowering / backend compile with a
  ``/jax/core/compile/...`` duration event; a registered listener
  counts them (and accumulates compile seconds) process-wide.  These
  events carry no function identity, so they answer "how much compiling is this run doing", not "who".
- ``wrap(fn, name)``: the per-function fallback.  The wrapper bumps
  ``counts[name]`` from INSIDE the function body, so under ``jax.jit``
  it fires exactly once per trace (a cache hit never re-enters the
  Python body) — wrap first, then jit.  ``retraces()`` reports
  ``count - 1`` per name: the first compile is expected, everything
  after is a retrace worth explaining (donation-shape drift, changing
  static args, weak-type flips...).

Both feed ``kind: "retrace"`` records into the telemetry flush, and
``python -m apex_tpu.telemetry summarize`` renders them next to the
step table.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, List, Optional

COMPILE_EVENT_PREFIX = "/jax/core/compile"
# one per program handed to the backend compiler (a persistent-cache
# hit included): ``events[BACKEND_COMPILE_EVENT]`` counts compilations
BACKEND_COMPILE_EVENT = COMPILE_EVENT_PREFIX + "/backend_compile_duration"


class RetraceCounter:
    def __init__(self):
        self.counts: Dict[str, int] = collections.Counter()
        self.events: Dict[str, int] = collections.Counter()
        self.compile_secs: float = 0.0
        self._listener = None
        # the monitoring listener fires on whatever thread triggers a
        # compile (a DeadlineRunner worker arming a dispatch, an async
        # checkpoint writer's first device_get) while the reporting
        # side reads from the flush thread — every counter touch takes
        # this lock (APX1001)
        self._lock = threading.Lock()

    # ---- jax.monitoring hook --------------------------------------------
    def install(self) -> bool:
        """Register the process-wide compile-event listener.
        Idempotent; always returns True."""
        if self._listener is not None:
            return True
        from jax import monitoring

        def _on_duration(event, duration, **kwargs):
            if event.startswith(COMPILE_EVENT_PREFIX):
                with self._lock:
                    self.events[event] += 1
                    self.compile_secs += float(duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
        self._listener = _on_duration
        return True

    def uninstall(self) -> None:
        if self._listener is None:
            return
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listener)
        self._listener = None

    # ---- per-function wrapper -------------------------------------------
    def wrap(self, fn, name: Optional[str] = None):
        """Count traces of ``fn``: wrap BEFORE jitting.  Under jit the
        bump runs once per (re)trace; called eagerly it counts calls."""
        label = name or getattr(fn, "__qualname__", None) \
            or getattr(fn, "__name__", "fn")

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self._lock:
                self.counts[label] += 1
            return fn(*args, **kwargs)

        return wrapped

    # ---- reporting --------------------------------------------------------
    def traces(self) -> int:
        """Process-wide trace count seen via jax.monitoring."""
        with self._lock:
            return self.events.get(
                COMPILE_EVENT_PREFIX + "/jaxpr_trace_duration", 0)

    def retraces(self) -> Dict[str, int]:
        """Per wrapped function: traces beyond the expected first."""
        with self._lock:
            counts = dict(self.counts)
        return {k: v - 1 for k, v in sorted(counts.items()) if v > 1}

    def records(self, step=None) -> List[dict]:
        out = []
        base = {"step": step} if step is not None else {}
        with self._lock:
            counts = dict(self.counts)
            compile_secs = self.compile_secs
            traces = self.events.get(
                COMPILE_EVENT_PREFIX + "/jaxpr_trace_duration", 0)
        if self._listener is not None:
            out.append({"kind": "retrace", "name": "<process>",
                        "traces": traces,
                        "compile_s": round(compile_secs, 3), **base})
        for name, n in sorted(counts.items()):
            out.append({"kind": "retrace", "name": name, "traces": n,
                        "retraces": n - 1, **base})
        return out
