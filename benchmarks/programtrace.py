"""The program's view of a traced run: the library's own host spans
(``apex/<layer>/<what>``, ``telemetry.span``) and, for each device
operation, the ``apex_<layer>/<phase>`` scope it was traced under
(``jax.named_scope`` in the step programs).  Loaded once per run and
cached on the readers' ``ctx``.

How an op event is joined to its scope (read by hand on a TPU v5e
trace, jax 0.9.0, libtpu 0.0.34, PR 25): every event of the device
plane's ``XLA Ops`` line points at an ``XEventMetadata`` whose stats
carry ``tf_op`` — the framework op name, ``jit(step)/transpose(jvp(
apex_layernorm))/apex_fused_layer_norm_bwd/pallas_call:`` — and
``program_id``, the number in the module's name ``jit_step(<id>)``.
``jax.profiler.ProfileData`` shows an event's own stats only, not its
metadata's, so this module reads the ``.xplane.pb`` itself: a
protobuf wire reader for the seven messages of ``xplane.proto``
(XSpace, XPlane, XLine, XEvent, XEventMetadata, XStatMetadata, XStat),
nothing beyond the standard library.  Times are nanoseconds on the
clock ``traceread`` uses (a line's ``timestamp_ns`` plus the event's
``offset_ps``).

``run.py`` hands readers no path: the trace is the newest
``.xplane.pb`` under ``<checkout>/.bench_trace/`` (run.py clears its
directory before the run and removes it after the readers).

A program without the spans or scopes (any commit before PR 25) gives
an empty view; every reader then returns nothing.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import traceread

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOST_PREFIXES = ("apex/", "PjitFunction(")
SCOPE_PREFIX = "apex_"

# name, start_ns, end_ns, the host line (thread) it was on
HostEvent = Tuple[str, float, float, str]
# op_name (framework name, "" where the trace has none), start_ns,
# end_ns, the jitted function of its module ("" where unknown)
OpEvent = Tuple[str, float, float, str]


# ---- which scope an operation belongs to ------------------------------------

_WRAPPERS = re.compile(r"\b(?:jvp|transpose|vmap)\(|\)")


def scope_path(op_name: str) -> Optional[Tuple[str, ...]]:
    """The components of ``op_name`` from its innermost ``apex_*`` scope
    on, or None where it lies under none.

    JAX wraps the name stack of differentiated code (``jvp(...)``,
    ``transpose(jvp(...))``, ``vmap(...)``); those wrappers and their
    closing brackets are stripped first, so an op of the backward pass
    resolves to the same scope as its forward.  A Pallas kernel's own
    name (the component Pallas puts before ``pallas_call``:
    ``apex_flash_attention_fwd``) is a kernel, not a scope."""
    parts = _WRAPPERS.sub("", op_name.rpartition(":")[0] or op_name
                          ).split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i].startswith(SCOPE_PREFIX) and not (
                i + 1 < len(parts) and parts[i + 1] == "pallas_call"):
            return tuple(parts[i:])
    return None


def under(path: Optional[Tuple[str, ...]], scopes: Iterable[str]) -> bool:
    """Whether ``path`` (of ``scope_path``) lies under one of ``scopes``
    (``apex_optim/trust_ratio``, or a whole layer: ``apex_layernorm``)."""
    if path is None:
        return False
    for scope in scopes:
        want = tuple(scope.split("/"))
        if path[:len(want)] == want:
            return True
    return False


# ---- the view ---------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    """``host``: the library's spans and JAX's ``PjitFunction(...)``
    dispatch events, by start; ``ops``: chip number -> the ``XLA Ops``
    events with their framework names."""
    host: List[HostEvent]
    ops: Dict[int, List[OpEvent]]

    def to_json(self) -> dict:
        return {"host": self.host,
                "ops": {str(k): v for k, v in self.ops.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "ProgramTrace":
        return cls(
            [(str(n), float(s), float(e), str(ln))
             for n, s, e, ln in obj["host"]],
            {int(k): [(str(n), float(s), float(e), str(p))
                      for n, s, e, p in rows]
             for k, rows in obj["ops"].items()})

    def spans(self, prefix: str = "apex/") -> List[HostEvent]:
        return [h for h in self.host if h[0].startswith(prefix)]


def self_times(events: Sequence[OpEvent]) -> List[Tuple[OpEvent, float]]:
    """(event, nanoseconds of its own) for events of one line: an
    event that encloses others (a ``while`` around its body's ops)
    keeps only what its children leave, so a sum never counts a
    nanosecond twice."""
    out: List[list] = []
    stack: List[list] = []          # rows of ``out`` still open
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            stack.pop()
        if stack:
            stack[-1][1] -= min(ev[2], stack[-1][0][2]) - ev[1]
        row = [ev, ev[2] - ev[1]]
        out.append(row)
        stack.append(row)
    return [(ev, max(ns, 0.0)) for ev, ns in out]


def find_xplane() -> Optional[str]:
    found = glob.glob(os.path.join(ROOT, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(ctx) -> Optional[ProgramTrace]:
    """The view of this run's trace, read once and kept on ``ctx``;
    None for a run without a trace."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "program_trace"):
        path = find_xplane()
        ctx.program_trace = None if path is None else load_xplane(path)
        if ctx.program_trace is not None:
            print("program trace:", nesting(ctx.program_trace,
                                            ctx.trace.host),
                  file=sys.stderr, flush=True)
    return ctx.program_trace


def scoped_ops(ctx, pt: ProgramTrace
               ) -> List[Tuple[Optional[Tuple[str, ...]], str, float]]:
    """(scope path, program, own nanoseconds) of each operation of the
    lowest-numbered chip inside the steady window; worked out once and
    kept on ``ctx``."""
    if ctx.steady is None or not pt.ops:
        return []
    if not hasattr(ctx, "program_ops"):
        ops = [(n, max(s, ctx.steady.start), min(e, ctx.steady.end), p)
               for n, s, e, p in pt.ops[min(pt.ops)]
               if e > ctx.steady.start and s < ctx.steady.end]
        ctx.program_ops = [(scope_path(op[0]), op[3], own)
                           for op, own in self_times(ops)]
    return ctx.program_ops


def nesting(pt: ProgramTrace, harness: Sequence[traceread.Event]) -> dict:
    """For each of the library's spans, the span that encloses it on
    the trace's clock — another of the library's, else the harness's
    (``benchmarks/jobs.py``), else ``(none)`` — with counts: the check
    that the library's spans lie where their calls are made."""
    out: Dict[str, Dict[str, int]] = {}
    mine = pt.spans()
    for name, s, e, line in mine:
        cover = [h for h in mine
                 if h[3] == line and h[1] <= s and e <= h[2]
                 and (h[1], h[2], h[0]) != (s, e, name)]
        if not cover:
            cover = [h for h in harness if h[1] <= s and e <= h[2]]
        parent = max(cover, key=lambda h: h[1])[0] if cover else "(none)"
        row = out.setdefault(name, {})
        row[parent] = row.get(parent, 0) + 1
    return out


# ---- reading the file -------------------------------------------------------

def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: ints for
    varints, memoryviews for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf):
    """XStat -> (metadata id, value) for the kinds read here: integers
    as they are, strings decoded, a ``ref_value`` as ("ref", id)."""
    key = val = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = ("ref", v)
    return key, val


def _map_entry(buf):
    key = val = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf):
    """-> (name, lines, event metadata, stat names); a line is (name,
    timestamp_ns, [(metadata id, offset_ps, duration_ps)]), an event's
    metadata (name, {stat name: value})."""
    name, lines, raw_meta, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, m = _map_entry(v)
            raw_meta[k] = m
        elif f == 5:
            k, m = _map_entry(v)
            for g, _, w in _fields(m):
                if g == 2:
                    stat_names[k] = bytes(w).decode()
    return name, lines, raw_meta, stat_names


def _event_metadata(buf, stat_names):
    name, stats = "", {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif f == 5:
            k, val = _stat(v)
            if isinstance(val, tuple):          # a string kept once
                val = stat_names.get(val[1], "")
            stats[stat_names.get(k, k)] = val
    return name, stats


def _line(buf):
    name, t0, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for g, _, w in _fields(v):
                if g == 1:
                    mid = w
                elif g == 2:
                    off = _signed(w)
                elif g == 3:
                    dur = _signed(w)
            events.append((mid, off, dur))
    return name, t0, events


def load_xplane(path: str) -> ProgramTrace:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    host: List[HostEvent] = []
    ops: Dict[int, List[OpEvent]] = {}
    for f, _, plane in _fields(space):
        if f != 1:
            continue
        pname, lines, raw_meta, stat_names = _plane(plane)
        chip = traceread.DEVICE_PLANE.match(pname)
        if not chip and pname != traceread.HOST_PLANE:
            continue
        meta = {k: _event_metadata(m, stat_names)
                for k, m in raw_meta.items()}
        parsed = [_line(ln) for ln in lines]
        if chip:
            # program id -> jitted function, from ``jit_<fn>(<id>)``
            programs = {}
            for lname, _, events in parsed:
                if lname == traceread.MODULE_LINE:
                    for mid, _, _ in events:
                        m = re.match(r"^jit_(.*)\((\d+)\)$",
                                     meta.get(mid, ("", {}))[0])
                        if m:
                            programs[int(m.group(2))] = m.group(1)
            rows = ops.setdefault(int(chip.group(1)), [])
            for lname, t0, events in parsed:
                if lname != traceread.OP_LINE:
                    continue
                for mid, off, dur in events:
                    stats = meta.get(mid, ("", {}))[1]
                    start = t0 + off / 1e3
                    rows.append((str(stats.get("tf_op", "")), start,
                                 start + dur / 1e3,
                                 programs.get(stats.get("program_id"), "")))
        else:
            for lname, t0, events in parsed:
                for mid, off, dur in events:
                    name = meta.get(mid, ("", {}))[0]
                    if name.startswith(HOST_PREFIXES):
                        start = t0 + off / 1e3
                        host.append((name, start, start + dur / 1e3,
                                     lname))
    return ProgramTrace(sorted(host, key=lambda h: h[1]), ops)
