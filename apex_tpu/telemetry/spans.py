"""Host-side spans: the library's one span primitive, on the profiler's
own clock.

``span(name)`` wraps a host-side region in a
``jax.profiler.TraceAnnotation``: while a ``jax.profiler`` trace is
running the region appears on the host plane (``/host:CPU``) under
``name``, on the same clock as the device's ``XLA Modules`` / ``XLA
Ops`` lines, so a device-idle gap can be attributed to what the host
was doing in it.  ``span(name, step=i)`` also tags the event with the
step, so the spans of one step share an identifier.  With no profiler
running and no sink registered a span costs the annotation's
enter/exit and nothing else: no clock reading, no record.

With a sink registered (:func:`add_sink`; the active
:class:`~.session.Telemetry` session registers one, aggregating into
per-name count/total/max stats that ride the next window flush as
``kind: "span"`` records) each span also becomes a
:class:`SpanRecord` — name, start, end, the enclosing span on this
thread, the step — handed to every sink as ``fn(name, record)`` when
the span closes.  Those times are ``perf_counter`` seconds: a
profile's clock starts with the trace, so a record can be compared
with other records but not with a device event; the profiler event is
the carrier that shares the device's clock.

Names of the library's own spans are ``apex/<layer>/<what>``
(docs/observability.md lists them); callers' spans keep theirs.

Spans are HOST timing by design: they may (and often do) contain
device syncs of their own (a checkpoint save device_gets the params).
Never open a span inside jitted code — the body would be measured at
trace time; a phase INSIDE a program is a ``jax.named_scope``
(``apex_<layer>/<phase>``).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

from apex_tpu.telemetry._sinks import SinkRegistry

_registry = SinkRegistry()
add_sink = _registry.add
remove_sink = _registry.remove

_tls = threading.local()


class SpanRecord(NamedTuple):
    """What a sink gets of one closed span (``perf_counter`` seconds)."""
    name: str
    start: float
    end: float
    parent: Optional[str]
    step: Optional[int]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _RecordedSpan:
    """The annotation plus a record for the sinks (only built while a
    sink is registered).  Exception-safe: the record is emitted and the
    parent stack popped even when the body raises."""

    __slots__ = ("_annotation", "_name", "_step", "_parent", "_start")

    def __init__(self, annotation, name, step):
        self._annotation, self._name, self._step = annotation, name, step

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self._name)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        _tls.stack.pop()
        _registry.emit(self._name, SpanRecord(
            self._name, self._start, end, self._parent, self._step))
        return False


def span(name: str, step: Optional[int] = None):
    """Context manager for a host-side region under ``name`` (nestable).

    Always a profiler event; a :class:`SpanRecord` for the sinks only
    while one is registered."""
    annotation = (jax.profiler.TraceAnnotation(name) if step is None
                  else jax.profiler.TraceAnnotation(name, step=step))
    if not _registry.active():
        return annotation
    return _RecordedSpan(annotation, name, step)


class SpanStats:
    """Per-name aggregate a session keeps between flushes."""

    def __init__(self):
        self._stats: Dict[str, List[float]] = {}
        self._lock = threading.Lock()

    def add(self, name: str, record: SpanRecord) -> None:
        seconds = record.seconds
        with self._lock:
            st = self._stats.setdefault(name, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += seconds
            st[2] = max(st[2], seconds)

    def records(self, step=None) -> List[dict]:
        """Cumulative ``kind: "span"`` records (one per name)."""
        with self._lock:
            return [{"kind": "span", "name": name, "count": st[0],
                     "total_ms": round(st[1] * 1e3, 3),
                     "max_ms": round(st[2] * 1e3, 3),
                     **({"step": step} if step is not None else {})}
                    for name, st in sorted(self._stats.items())]
