"""Shared host-side sink registry — the one fan-out mechanism behind
both :mod:`~apex_tpu.telemetry.spans` (span records) and
:mod:`~apex_tpu.telemetry.hostmetrics` (counters).  Each keeps its own
registry INSTANCE (a span sink must never see counter values), but the
registration/emission semantics live here once.
"""

from __future__ import annotations

import threading
from typing import Callable, List


class SinkRegistry:
    """Thread-safe list of ``fn(name, value)`` callbacks.

    ``emit`` is a truthiness no-op with no sinks registered (the
    ``_tape`` discipline: library code never pays for telemetry that
    is off) and calls sinks outside the lock, so a slow sink cannot
    block registration from another thread.
    """

    def __init__(self):
        self._sinks: List[Callable[[str, float], None]] = []
        self._lock = threading.Lock()

    def add(self, fn: Callable[[str, float], None]) -> None:
        with self._lock:
            # all mutation happens under _lock; the one unlocked
            # access is emit's truthiness fast path, a deliberate
            # GIL-atomic read so disabled telemetry costs nothing
            self._sinks.append(fn)   # apexlint: disable=APX1001

    def remove(self, fn: Callable[[str, float], None]) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def active(self) -> bool:
        """Whether any sink is registered (the same unlocked,
        GIL-atomic read as ``emit``'s fast path)."""
        return bool(self._sinks)

    def emit(self, name: str, value: float) -> None:
        if not self._sinks:
            return
        with self._lock:
            sinks = list(self._sinks)
        for fn in sinks:
            fn(name, value)
