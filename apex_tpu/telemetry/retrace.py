"""Compilation accounting: the runtime companion to the APX30x rules,
and the account of what a process's set-up was made of.

apexlint's APX301-303 flag retrace *hazards* statically; this module
counts the tracing, lowering and compiling that actually happens.

- ``jax.monitoring``: JAX stamps every trace, lowering and backend
  compile with a ``/jax/core/compile/...`` event that carries its
  duration, its start and end (``time.time()`` seconds) and, in jax
  0.9.0, the ``fun_name`` of what was compiled.  A
  :class:`RetraceCounter` that is installed keeps, process-wide: the
  count and seconds of each kind; a :class:`CompileSpan` for every
  event that was not nested in another on its thread (a function
  traced while ``step`` is traced is part of tracing ``step``), so the
  kept spans of one thread never overlap and their seconds add up to
  wall time; from the persistent cache its hits, misses and retrieval
  seconds, each hit or miss also on the backend span of the program it
  was for.  ``program_rows()`` groups the spans by program.
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

:func:`process` is the one :class:`ProcessAccount` of the process,
installed when ``apex_tpu`` is imported: a counter that also takes the
stamp of each optimizer step's beginning (:func:`mark_step`, from
``FusedOptimizerBase.step``) and the start and end of the library's
construction phases (:func:`phase`), and that closes itself — its
listeners unregistered, every later mark one attribute test — after
``MAX_STEPS`` steps or ``MAX_SPANS`` kept spans.  ``until_step(k)``
sums what ended before step ``k`` began, ``first_quiet_step()`` is the
first step during which nothing was traced or loaded, ``report()`` is
the table an operator reads (docs/observability.md, "Retrace
counting").
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from apex_tpu.telemetry.spans import span

COMPILE_EVENT_PREFIX = "/jax/core/compile"
TRACE_EVENT = COMPILE_EVENT_PREFIX + "/jaxpr_trace_duration"
LOWER_EVENT = COMPILE_EVENT_PREFIX + "/jaxpr_to_mlir_module_duration"
# one per program handed to the backend compiler (a persistent-cache
# hit included): ``events[BACKEND_COMPILE_EVENT]`` counts compilations
BACKEND_COMPILE_EVENT = COMPILE_EVENT_PREFIX + "/backend_compile_duration"
KINDS = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
         BACKEND_COMPILE_EVENT: "backend"}

CACHE_EVENT_PREFIX = "/jax/compilation_cache"
# a hit: the executable was read from the persistent cache; a miss: it
# was compiled AND written there (one compiled under the cache's size
# or compile-time threshold is neither)
CACHE_HIT_EVENT = CACHE_EVENT_PREFIX + "/cache_hits"
CACHE_MISS_EVENT = CACHE_EVENT_PREFIX + "/cache_misses"
CACHE_RETRIEVAL_EVENT = CACHE_EVENT_PREFIX + "/cache_retrieval_time_sec"

MAX_STEPS = 16          # the process account's two caps
MAX_SPANS = 4096


class CompileSpan(NamedTuple):
    """One trace / lowering / backend compile that no other enclosed on
    its thread; ``start`` and ``end`` are jax's ``time.time()`` stamps."""
    kind: str                   # "trace" | "lower" | "backend"
    name: str                   # jax's fun_name less its "jit(...)"
    start: float
    end: float
    thread: int
    cache: Optional[str] = None  # a backend span's "hit" | "miss"
    retrieval_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _program_name(fun_name) -> str:
    """Tracing says ``step`` where lowering and the backend say
    ``jit(step)``: one row a program needs one name."""
    name = str(fun_name) if fun_name else "?"
    head, paren, rest = name.partition("(")
    if paren and rest.endswith(")") and head.isidentifier():
        return rest[:-1]
    return name


def _by_kind(spans) -> Dict[str, float]:
    out = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
           "trace_n": 0, "lower_n": 0, "backend_n": 0}
    for sp in spans:
        out[sp.kind + "_s"] += sp.seconds
        out[sp.kind + "_n"] += 1
    return out


def _program_rows(spans) -> Dict[str, dict]:
    rows: Dict[str, dict] = {}
    for sp in spans:
        row = rows.setdefault(sp.name, {
            "traces": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "compile_s": 0.0, "cache": None})
        row["traces"] += sp.kind == "trace"
        row[sp.kind + "_s"] += sp.seconds
        row["compile_s"] += sp.seconds
        if sp.cache is not None and row["cache"] != "miss":
            row["cache"] = sp.cache
    return rows


def _slowest(rows: Dict[str, dict], n: int) -> List[tuple]:
    return sorted(rows.items(), key=lambda kv: -kv[1]["compile_s"])[:n]


class RetraceCounter:
    def __init__(self, max_spans: int = MAX_SPANS):
        self.counts: Dict[str, int] = collections.Counter()
        # by jax's event name: how many, and their durations summed (a
        # nested trace is in its own and in its encloser's)
        self.events: Dict[str, int] = collections.Counter()
        self.seconds: Dict[str, float] = collections.Counter()
        self.spans: List[CompileSpan] = []
        self.max_spans = max_spans
        self.spans_dropped = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_retrieval_s = 0.0
        # thread -> [hit or miss, retrieval seconds] the cache reported
        # for the program that thread's backend compile is in
        self._cache_pending: Dict[int, list] = {}
        self._listeners = None
        # the monitoring listeners fire on whatever thread triggers a
        # compile (a DeadlineRunner worker arming a dispatch, an async
        # checkpoint writer's first device_get) while the reporting
        # side reads from the flush thread — every counter touch takes
        # this lock (APX1001)
        self._lock = threading.Lock()

    @property
    def compile_secs(self) -> float:
        """Seconds of every ``/jax/core/compile`` event, summed."""
        with self._lock:
            return float(sum(self.seconds.values()))

    # ---- jax.monitoring hooks -------------------------------------------
    def install(self) -> bool:
        """Register the process-wide listeners.  Idempotent; always
        returns True."""
        if self._listeners is not None:
            return True
        from jax import monitoring

        self._listeners = (self._on_time_span, self._on_duration,
                           self._on_event)
        monitoring.register_event_time_span_listener(self._listeners[0])
        monitoring.register_event_duration_secs_listener(self._listeners[1])
        monitoring.register_event_listener(self._listeners[2])
        return True

    def uninstall(self) -> None:
        if self._listeners is None:
            return
        from jax import monitoring
        on_time_span, on_duration, on_event = self._listeners
        monitoring.unregister_event_time_span_listener(on_time_span)
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
        self._listeners = None

    def _on_time_span(self, event, start, end, **kwargs):
        if not event.startswith(COMPILE_EVENT_PREFIX):
            return
        kind = KINDS.get(event)
        name = _program_name(kwargs.get("fun_name"))
        thread = threading.get_ident()
        with self._lock:
            self.events[event] += 1
            self.seconds[event] += end - start
            if kind is None:
                return
            cache, retrieval_s = (
                self._cache_pending.pop(thread, (None, 0.0))
                if kind == "backend" else (None, 0.0))
            self._keep(CompileSpan(kind, name, float(start), float(end),
                                   thread, cache, retrieval_s))

    def _keep(self, new: CompileSpan) -> None:
        """Append ``new`` in place of the spans it encloses (lock
        held).  Events arrive as they end, so whatever ended after
        ``new`` began is at the tail; of those, the ones that also
        began after it on its thread ran inside it."""
        spans = self.spans
        i = len(spans)
        while i and spans[i - 1].end >= new.start:
            i -= 1
        tail = [sp for sp in spans[i:]
                if sp.thread != new.thread or sp.start < new.start]
        if len(tail) + 1 + i > self.max_spans:
            self.spans_dropped += 1
            return
        spans[i:] = tail + [new]

    def _on_duration(self, event, duration, **kwargs):
        if event == CACHE_RETRIEVAL_EVENT:
            with self._lock:
                self.cache_retrieval_s += float(duration)
                self._cache_pending.setdefault(
                    threading.get_ident(), [None, 0.0])[1] += float(duration)

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT or event == CACHE_MISS_EVENT:
            hit = event == CACHE_HIT_EVENT
            with self._lock:
                self.cache_hits += hit
                self.cache_misses += not hit
                self._cache_pending.setdefault(
                    threading.get_ident(), [None, 0.0])[0] = (
                        "hit" if hit else "miss")

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
            return self.events.get(TRACE_EVENT, 0)

    def retraces(self) -> Dict[str, int]:
        """Per wrapped function: traces beyond the expected first."""
        with self._lock:
            counts = dict(self.counts)
        return {k: v - 1 for k, v in sorted(counts.items()) if v > 1}

    def program_rows(self) -> Dict[str, dict]:
        """program -> its kept spans' seconds by kind, how often it was
        traced, and whether the cache had it."""
        with self._lock:
            return _program_rows(self.spans)

    def records(self, step=None, programs: int = 16) -> List[dict]:
        """``kind: "retrace"`` records: the process's row, the
        ``programs`` slowest programs' (``program/<name>``) and the
        wrapped functions'."""
        out = []
        base = {"step": step} if step is not None else {}
        with self._lock:
            counts = dict(self.counts)
            events, seconds = dict(self.events), dict(self.seconds)
            rows = _program_rows(self.spans)
            cache = {"cache_hits": self.cache_hits,
                     "cache_misses": self.cache_misses,
                     "cache_retrieval_s": round(self.cache_retrieval_s, 3)}
        if self._listeners is not None or events:
            out.append({"kind": "retrace", "name": "<process>",
                        "traces": events.get(TRACE_EVENT, 0),
                        "lowerings": events.get(LOWER_EVENT, 0),
                        "compiles": events.get(BACKEND_COMPILE_EVENT, 0),
                        "compile_s": round(sum(seconds.values()), 3),
                        "trace_s": round(seconds.get(TRACE_EVENT, 0.0), 3),
                        "lower_s": round(seconds.get(LOWER_EVENT, 0.0), 3),
                        "backend_s": round(
                            seconds.get(BACKEND_COMPILE_EVENT, 0.0), 3),
                        "programs": len(rows), **cache, **base})
            for name, row in sorted(_slowest(rows, programs)):
                out.append({"kind": "retrace", "name": "program/" + name,
                            **{k: round(v, 3) if isinstance(v, float) else v
                               for k, v in row.items()}, **base})
        for name, n in sorted(counts.items()):
            out.append({"kind": "retrace", "name": name, "traces": n,
                        "retraces": n - 1, **base})
        return out


class ProcessAccount(RetraceCounter):
    """A counter that also knows where the optimizer's steps began and
    what the library's construction phases covered, for as long as it
    is ``open``: from ``install()`` until ``max_steps`` steps were
    marked or ``max_spans`` spans kept.  Its memory is bounded by the
    two; closed, it keeps what it has and costs callers one attribute
    test."""

    def __init__(self, max_steps: int = MAX_STEPS,
                 max_spans: int = MAX_SPANS):
        super().__init__(max_spans)
        self.max_steps = max_steps
        self.started = time.time()
        self.open = False
        self.marks: List[float] = []
        # (name, start, end, thread), time.time() seconds
        self.phases: List[tuple] = []
        # time.time() of each trace of a kernel program (kernel_trace)
        self.kernel_marks: List[float] = []
        self._reported = False

    def install(self) -> bool:
        self.open = True
        return super().install()

    def close(self) -> None:
        self.open = False
        self.uninstall()

    # ---- marks -------------------------------------------------------------
    def mark_step(self) -> None:
        """An optimizer step begins (any optimizer's: they share the
        process's clock)."""
        # full (a span was dropped): the listeners go here and not where
        # it happened, inside jax's walk over its listeners, which would
        # then skip the listener after
        if self.spans_dropped:
            return self.close()
        with self._lock:
            self.marks.append(time.time())
            done = len(self.marks) >= self.max_steps
        if done:
            self.close()

    def kernel_trace(self) -> None:
        """A kernel program (``ops/_dispatch.py:lowered_once``) is
        traced.  A trace is lowered once in each program that holds it,
        so in a process of several such programs the lowerings are more
        than the traces."""
        with self._lock:
            if len(self.kernel_marks) < self.max_spans:
                self.kernel_marks.append(time.time())

    def add_phase(self, name: str, start: float, end: float) -> None:
        with self._lock:
            if len(self.phases) < self.max_spans:
                self.phases.append((name, start, end,
                                    threading.get_ident()))

    # ---- reading -----------------------------------------------------------
    def summary(self, before: Optional[float] = None) -> dict:
        """What ended before ``before`` (``time.time()`` seconds;
        everything kept, by default): seconds and counts by kind over
        the kept spans (wall time: they do not overlap), the cache's
        hits and misses among them, each phase's ``seconds`` with the
        kinds' seconds inside it and ``own_s``, what is left of it
        without them, the programs' rows, and ``kernel_traces``: how
        often a kernel program was traced."""
        with self._lock:
            spans, phases = list(self.spans), list(self.phases)
            kernels = list(self.kernel_marks)
        if before is not None:
            spans = [sp for sp in spans if sp.end <= before]
            phases = [ph for ph in phases if ph[2] <= before]
            kernels = [t for t in kernels if t <= before]
        backend = [sp for sp in spans if sp.kind == "backend"]
        out = _by_kind(spans)
        out.update(
            cache_hits=sum(sp.cache == "hit" for sp in backend),
            cache_misses=sum(sp.cache == "miss" for sp in backend),
            cache_retrieval_s=sum(sp.retrieval_s for sp in backend),
            programs=_program_rows(spans), phases={},
            kernel_traces=len(kernels))
        for name, start, end, thread in phases:
            inside = _by_kind(sp for sp in spans if sp.thread == thread
                              and sp.start >= start and sp.end <= end)
            row = out["phases"].setdefault(
                name, dict.fromkeys(inside, 0) | {"seconds": 0.0})
            row["seconds"] += end - start
            for k, v in inside.items():
                row[k] += v
        for row in out["phases"].values():
            # what the phase spent outside the compile events inside it
            row["own_s"] = (row["seconds"] - row["trace_s"] - row["lower_s"]
                            - row["backend_s"])
        return out

    def until_step(self, k: int) -> Optional[dict]:
        """``summary`` of everything before optimizer step ``k`` (from
        0) began, with ``wall_s`` from this account's start to that
        moment; None while that step has not begun, or began after the
        account closed."""
        with self._lock:
            mark = self.marks[k] if 0 <= k < len(self.marks) else None
        if mark is None:
            return None
        return {"wall_s": mark - self.started, **self.summary(mark)}

    def first_quiet_step(self) -> Optional[int]:
        """The first step from whose beginning to the next step's
        nothing was traced, lowered, loaded or compiled."""
        with self._lock:
            marks, spans = list(self.marks), list(self.spans)
        for k, (t0, t1) in enumerate(zip(marks, marks[1:])):
            if not any(sp.end > t0 and sp.start < t1 for sp in spans):
                return k
        return None

    def report(self) -> str:
        """The account as a table: up to the first quiet step where
        there is one, of everything kept where there is not."""
        with self._lock:
            marks = list(self.marks)
        quiet = self.first_quiet_step()
        s = self.summary(None if quiet is None else marks[quiet])
        lines = ["set-up account (apex_tpu.telemetry.retrace): "
                 f"{len(marks)} steps marked, "
                 + ("open" if self.open else "closed")
                 + (f", {self.spans_dropped} spans dropped"
                    if self.spans_dropped else "")]

        def line(what, seconds, rest=""):
            lines.append(f"  {what:<34}{seconds:>9.3f} s  {rest}".rstrip())

        if marks:
            line("to the first step", marks[0] - self.started)
        if quiet is not None:
            line("to the first quiet step", marks[quiet] - self.started,
                 f"step {quiet}")
        line("trace", s["trace_s"], f"{s['trace_n']} outermost calls")
        line("lower", s["lower_s"], f"{s['lower_n']} programs, "
             f"{s['kernel_traces']} kernel programs traced")
        line("backend (load or compile)", s["backend_s"],
             f"{s['backend_n']} programs")
        line("  of it the cache's retrieval", s["cache_retrieval_s"],
             f"{s['cache_hits']} hits, {s['cache_misses']} misses")
        for name, row in s["phases"].items():
            line("phase " + name, row["seconds"],
                 "its own %.3f; trace %.3f, lower %.3f, backend %.3f inside"
                 % (row["own_s"], row["trace_s"], row["lower_s"],
                    row["backend_s"]))
        for name, row in _slowest(s["programs"], 5):
            line("program " + name, row["compile_s"],
                 "trace %.3f, lower %.3f, backend %.3f%s" % (
                     row["trace_s"], row["lower_s"], row["backend_s"],
                     f" ({row['cache']})" if row["cache"] else ""))
        return "\n".join(lines)

    def report_once(self) -> Optional[str]:
        """``report()`` the first time a quiet step is known (or the
        account closed without one); None before, and ever after."""
        if self._reported or (self.open
                              and self.first_quiet_step() is None):
            return None
        self._reported = True
        return self.report()


_PROCESS = ProcessAccount()


def process() -> ProcessAccount:
    """The process's account (``import apex_tpu`` installs it)."""
    return _PROCESS


def mark_step() -> None:
    """An optimizer step begins: a stamp while the account is open, one
    attribute test after."""
    if _PROCESS.open:
        _PROCESS.mark_step()


def kernel_trace() -> None:
    """A kernel program is traced: a stamp while the account is open."""
    if _PROCESS.open:
        _PROCESS.kernel_trace()


class _Phase:
    __slots__ = ("_account", "_name", "_span", "_start")

    def __init__(self, account, name):
        self._account, self._name = account, name

    def __enter__(self):
        self._span = span(self._name)
        self._span.__enter__()
        self._start = time.time()
        return self

    def __exit__(self, *exc):
        end = time.time()
        self._span.__exit__(*exc)
        self._account.add_phase(self._name, self._start, end)
        return False


def phase(name: str):
    """``telemetry.span(name)`` around work that runs once a process
    (the library's construction); while the account is open its start
    and end also go there, and the compile events of its thread between
    them read as inside it."""
    if not _PROCESS.open:
        return span(name)
    return _Phase(_PROCESS, name)


def phased(name: str):
    """Decorator: the call runs in ``phase(name)``."""
    def decorate(fn):
        @functools.wraps(fn)
        def in_phase(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)
        return in_phase
    return decorate
