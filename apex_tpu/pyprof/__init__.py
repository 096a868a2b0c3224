"""apex.pyprof parity shim (reference: historical apex/pyprof — BOTH
halves: the nvtx annotation toolkit wrapping torch functions with
torch.cuda.nvtx.range_push/pop, and the pyprof/prof parsers that
turned captured profiles into per-kernel tables; SURVEY.md §5
tracing).

TPU equivalents: `jax.named_scope` annotations + `jax.profiler` trace
capture (the nvtx half, `apex_tpu.pyprof.nvtx`), and the trace
distiller that parses the written profile into a top-device-ops table
(the prof half, `apex_tpu.pyprof.prof`).

Run-time training telemetry (metric rings, span timing, retrace
counters) is the sibling layer `apex_tpu.telemetry`.  The two do not
share a mechanism: an nvtx range here is a ``jax.named_scope``, a
trace-time name that reaches the ``op_name`` of operations traced
inside it and writes nothing into a running profiler by itself;
``telemetry.span(name)`` is a ``jax.profiler.TraceAnnotation``, a host
event in the profiler's trace.
"""

from apex_tpu.pyprof import nvtx, prof  # noqa: F401
from apex_tpu.pyprof.nvtx import annotate, profile  # noqa: F401

_enabled = False


def init():
    """Reference parity: pyprof.init() enabled global annotation.  Here
    named scopes are always legal; init just flips the marker flag."""
    global _enabled
    _enabled = True


def enabled() -> bool:
    return _enabled


__all__ = ["init", "enabled", "nvtx", "prof", "annotate", "profile"]
