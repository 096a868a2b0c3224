"""apex_tpu.telemetry — host-sync-free training telemetry.

The flat AMP pipeline computes every signal a production trainer
watches — global grad norm, overflow flag, clip coefficient, loss
scale, LAMB trust ratios — entirely on device; this package surfaces
them WITHOUT re-introducing the per-step ``device_get`` our own linter
flags as APX101 (and whose runtime twin is APX102).  Core invariant:
**zero additional host syncs per step**.

- :class:`MetricRing` (ring.py): a small device-resident
  ``(window+1, 2+n_metrics)`` f32 buffer jitted code writes by static
  metric column at a cursor-selected row; the host flushes it with ONE
  ``device_get`` every ``window`` recorded steps.
- :mod:`_tape` + :meth:`Telemetry.instrument`: producers through the
  stack (amp flat pipeline, fused optimizers, bucketed DDP reducer)
  report traced scalars into the step's tape; the instrument wrapper
  writes them into the ring inside the step's own jit.
- emitters (emitters.py): JSONL (schema'd, one record per step),
  rank-0 rate-limited console, wide CSV — all fed at flush time only.
- :func:`span` (spans.py): host-side spans (the optimizer's step
  call, the scaler update, checkpoint save/restore...), each a
  ``jax.profiler.TraceAnnotation`` — an event on the host plane of a
  running profiler trace, on the device ops' clock — and, while a sink
  is registered, a record (name, start, end, parent, step) for it.
- :class:`RetraceCounter` (retrace.py): counts recompiles at run time
  via ``jax.monitoring`` (plus a per-function wrapper fallback) — the
  runtime companion to the APX30x static rules.
- :class:`WatchedLock` (lockwatch.py): opt-in lock wrapper emitting
  ``lock/<name>/wait_ms`` / ``held_ms`` hostmetrics — the runtime
  companion to apexrace's APX100x lock-domain rules, free when no
  sink is registered.
- ``python -m apex_tpu.telemetry summarize <run_dir>...`` (cli.py):
  render a run's JSONL as step/span/retrace tables, stdlib-only
  (several run dirs merge host-tagged).
- :class:`MetricsServer` (export.py): live ``/metrics`` (Prometheus
  text) + ``/healthz`` over the flushed host state — zero added
  per-step device syncs.
- :mod:`incident` + :mod:`timeline` + ``python -m apex_tpu.telemetry
  timeline <dir>...``: one incident id threading a whole causal chain
  (anomaly/death -> action -> resize -> replay-complete) across every
  host's run dir, merged into one skew-corrected fleet timeline
  (text / ``--json`` / ``--chrome-trace`` for Perfetto).
- :mod:`profiler` (profiler/): the performance observatory — trace
  capture windows, device-time attribution (compute / collective /
  transfer / idle + overlap fraction), cost-model MFU, and
  ``python -m apex_tpu.telemetry profile <trace_dir>``.
- :mod:`reqtrace` + :mod:`hist`: request-level lifecycle traces for
  the serving path (enqueue -> admit -> decode windows -> typed
  verdict, ``kind:"reqtrace"`` records) and fixed-bucket log-scale
  SLO histograms (TTFT / e2e / inter-token / queue wait,
  ``kind:"hist"``) — streaming per replica, merged across run dirs,
  rendered as Prometheus histograms on ``/metrics`` and as async
  request lanes in the chrome trace.

See docs/observability.md for the producer -> metric wiring table and
the design rationale.
"""

from apex_tpu.telemetry import profiler
from apex_tpu.telemetry._tape import emit as emit_metric
from apex_tpu.telemetry.emitters import (CsvEmitter, Emitter,
                                         JsonlEmitter, StepLogger)
from apex_tpu.telemetry.export import MetricsServer
from apex_tpu.telemetry.hist import (HistogramSet, LatencyHistogram,
                                     prometheus_histogram_lines)
from apex_tpu.telemetry.incident import IncidentLog
from apex_tpu.telemetry.reqtrace import RequestTracer, trace_gaps
from apex_tpu.telemetry.lockwatch import WatchedLock
from apex_tpu.telemetry.retrace import RetraceCounter
from apex_tpu.telemetry.ring import MetricRing
from apex_tpu.telemetry.session import DEFAULT_METRICS, Telemetry
from apex_tpu.telemetry.spans import span

__all__ = [
    "MetricRing", "Telemetry", "DEFAULT_METRICS",
    "Emitter", "JsonlEmitter", "CsvEmitter", "StepLogger",
    "MetricsServer", "IncidentLog",
    "RetraceCounter", "WatchedLock", "span", "emit_metric",
    "LatencyHistogram", "HistogramSet", "prometheus_histogram_lines",
    "RequestTracer", "trace_gaps",
    "profiler",
]
