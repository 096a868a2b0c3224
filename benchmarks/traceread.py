"""From a profiler trace to the numbers the per-layer readers use.

A trace is read with ``jax.profiler.ProfileData`` (JAX alone, no
TensorFlow) into plain tuples, so the same reduction runs on a recorded
trace kept as JSON beside the tests.  The interval arithmetic is a copy
of ``apex_tpu/telemetry/profiler/attribution.py`` (``_merge``,
``_intersect``, ``top_ops``): busy time is the UNION of device-op
intervals, never a sum of durations.

What a TPU trace looks like (jax 0.9.0, libtpu 0.0.34, read by hand in
PR 24): one plane per chip, ``/device:TPU:<n>``; its line ``XLA
Modules`` carries one event per executed program, named
``jit_<function>(<fingerprint>)``; its line ``XLA Ops`` carries one
event per HLO instruction, named by the instruction's whole text
(``%fusion.12 = f32[...] fusion(...)``), a Mosaic kernel under the name
it was given (``%apex_flash_attention_fwd.3 = ...``).  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear there under
their own names.  All times are nanoseconds on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start_ns, end_ns


# ---- interval sets (copy of attribution.py's helpers) ---------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval of ``merged`` covers."""
    out, at = [], window[0]
    for s, e in intersect(merged, [window]):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


# ---- the trace --------------------------------------------------------------

@dataclasses.dataclass
class Trace:
    """``devices``: chip number -> line name -> events; ``host``: every
    event of the host plane's lines, whatever thread."""
    devices: Dict[int, Dict[str, List[Event]]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        def ev(rows):
            return [(str(n), float(s), float(e)) for n, s, e in rows]
        return cls({int(k): {ln: ev(rows) for ln, rows in lines.items()}
                    for k, lines in obj["devices"].items()},
                   ev(obj["host"]))


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load_xplane(path: str, host_names: Sequence[str] = ()) -> Trace:
    """Device planes whole (their module and op lines); of the host
    plane only the events named in ``host_names`` (the harness's own
    spans: the rest is the runtime's chatter, millions of events)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = set(host_names)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = devices.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [
                        (e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
    return Trace(devices, sorted(host, key=lambda e: e[1]))


def describe_xplane(path: str, per_line: int = 6) -> List[dict]:
    """Planes, lines and a few event names of a trace: what to look at
    by hand before trusting a reader on a new device or JAX version."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(events),
                        "names": [e.name for e in events[:per_line]]})
    return out


# ---- reductions -------------------------------------------------------------

def module_events(trace: Trace, device: int, program: str) -> List[Event]:
    """Executions of the program whose jitted function is ``program``
    (module names read ``jit_<program>`` or ``jit_<program>(<id>)``)."""
    pat = re.compile(r"^jit_" + re.escape(program) + r"(\(\d+\))?$")
    return sorted((e for e in trace.devices[device].get(MODULE_LINE, [])
                   if pat.match(e[0])), key=lambda e: e[1])


@dataclasses.dataclass
class Window:
    """A steady part of the trace holding whole steps: from the start
    of one execution of the step's first program to the start of a
    later one."""
    start: float
    end: float
    steps: int

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


def steady_window(trace: Trace, first_program: str,
                  skip: int = 0) -> Optional[Window]:
    """On the lowest-numbered chip: from the ``skip``-th start of
    ``first_program`` to its last start.  None with fewer than two."""
    if not trace.devices:
        return None
    starts = [e[1] for e in module_events(
        trace, min(trace.devices), first_program)][skip:]
    if len(starts) < 2:
        return None
    return Window(starts[0], starts[-1], len(starts) - 1)


def clipped(events: Iterable[Event], window: Window) -> List[Event]:
    out = []
    for name, s, e in events:
        s, e = max(s, window.start), min(e, window.end)
        if e > s:
            out.append((name, s, e))
    return out


def busy_seconds(trace: Trace, window: Window) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per_chip = [total(merge((s, e) for _, s, e in
                            clipped(lines.get(OP_LINE, []), window)))
                for lines in trace.devices.values()]
    return sum(per_chip) / len(per_chip) / 1e9 if per_chip else 0.0


def op_name(event_name: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[8]{0} fusion(...)``: the op
    line names an event by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_label(event_name: str) -> str:
    """The op's name with the type of its (first) result, which says
    more than ``fusion.12`` does: ``fusion.12 f32[28]``."""
    head, _, rhs = event_name.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[^\]]*\])", rhs)
    return op_name(head) + (" " + m.group(1) if m else "")


def top_ops(trace: Trace, window: Window, top: int = 10) -> List[list]:
    """[name, seconds] of the device operations that took most time on
    the lowest-numbered chip (duration sums, as ``top_ops`` of
    attribution.py)."""
    agg: Dict[str, float] = {}
    ops = trace.devices[min(trace.devices)].get(OP_LINE, [])
    for name, s, e in clipped(ops, window):
        key = op_label(name)
        agg[key] = agg.get(key, 0.0) + (e - s)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def idle_gaps(trace: Trace, window: Window, top: int = 10) -> List[list]:
    """[host span, seconds] of the idle time of the lowest-numbered
    chip, by the harness's span that covered the middle of each gap
    (``(no span)`` where none did)."""
    ops = trace.devices[min(trace.devices)].get(OP_LINE, [])
    busy = merge((s, e) for _, s, e in clipped(ops, window))
    agg: Dict[str, float] = {}
    for s, e in gaps(busy, (window.start, window.end)):
        mid = (s + e) / 2
        # the innermost (latest-started) span that covers the middle
        cover = [h for h in trace.host if h[1] <= mid < h[2]]
        name = max(cover, key=lambda h: h[1])[0] if cover else "(no span)"
        agg[name] = agg.get(name, 0.0) + (e - s)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def kernel_seconds(trace: Trace, window: Window, prefix: str) -> float:
    """Summed device time, on the lowest-numbered chip, of the
    operations whose name starts with ``prefix``."""
    ops = trace.devices[min(trace.devices)].get(OP_LINE, [])
    return sum(e - s for name, s, e in clipped(ops, window)
               if op_name(name).startswith(prefix)) / 1e9


def save_json(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
