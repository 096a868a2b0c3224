"""Programmatic ``jax.profiler`` trace windows.

One capture code path for the whole repo: the standalone
``tools/profile_step.py`` CLI and :func:`profile_window` below both
trace through :func:`trace` here, so the capture rules (device-only
tracing, warmup outside the window) are encoded once instead of being
a rule each caller must remember.

Why device-only: a default-options capture drowns a few hundred device
ops in ~1M host python events, so the host and python tracers are OFF.
The CPU backend is the one exception — its XLA executor pool IS a host
thread, recorded by the host tracer, so there level 1 stays on (python
tracer still off).  Compilation must happen BEFORE the window opens or
the trace times XLA, not the step.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Optional, Sequence

from apex_tpu.telemetry.profiler.events import META_NAME

__all__ = ["trace", "trace_options", "profile_window", "annotate_step"]


def trace_options():
    """``ProfileOptions`` for a device-timeline capture: python tracer
    off; host tracer off too except on the CPU backend, whose op
    timeline only the host tracer records (module docstring)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1 if jax.default_backend() == "cpu" else 0
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def trace(outdir: str, device_only: bool = True):
    """``jax.profiler.trace`` with the device-only defaults applied
    (module docstring).  One capture at a time per process: the
    profiler session is process-global."""
    import jax
    opts = trace_options() if device_only else None
    with jax.profiler.trace(outdir, profiler_options=opts):
        yield outdir


def annotate_step(step_fn, name: str = "train_step"):
    """Wrap a step in a named scope so captures show its boundary.

    This is the whole "profiler-capable" instrumentation surface: a
    trace-time annotation that lowers to NOTHING — no callbacks, no
    transfers, no added primitives (the ``profiler.annotated_step``
    apexverify spec and the ``profiler_overhead`` kernel-bench row
    both hold it to that).  Capture-off profiling costs zero."""
    import functools

    import jax

    @functools.wraps(step_fn)
    def annotated(*args, **kwargs):
        with jax.named_scope(name):
            return step_fn(*args, **kwargs)
    return annotated


def _block_on(x) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def profile_window(step_fn, state: Any, batch: Sequence = (), *,
                   steps: int = 3, outdir: str,
                   thread_state: bool = False,
                   want_flops: bool = True,
                   extra_meta: Optional[dict] = None) -> dict:
    """Capture a trace of ``steps`` executions of
    ``step_fn(state, *batch)`` and write the :data:`META_NAME` sidecar
    the report layer needs for MFU (step count, cost-analysis FLOPs,
    chip spec).

    ``step_fn`` should be jitted (FLOPs come from its compiled cost
    analysis; a plain callable still captures, with ``flops_per_step``
    null).  One warmup call runs BEFORE the window so compilation is
    never inside the trace.  ``thread_state=True`` feeds each call's
    first output back as ``state`` (donating steps need this — a
    donated buffer cannot be passed twice).  Returns the meta dict.

    The wall-clock ``step_ms`` recorded here includes dispatch
    overhead; the device-timeline numbers in
    ``python -m apex_tpu.telemetry profile <outdir>`` are the honest
    breakdown.
    """
    import jax

    from apex_tpu.telemetry.profiler.mfu import chip_spec, step_flops

    os.makedirs(outdir, exist_ok=True)

    flops = None
    if want_flops and hasattr(step_fn, "lower"):
        flops = step_flops(step_fn, state, *batch)

    out = step_fn(state, *batch)            # warmup: compile outside
    _block_on(out)
    if thread_state:
        state = out[0] if isinstance(out, tuple) else out

    t0 = time.perf_counter()
    with trace(outdir):
        for _ in range(steps):
            out = step_fn(state, *batch)
            if thread_state:
                state = out[0] if isinstance(out, tuple) else out
        # one sync, inside the window, so the trace contains every
        # step's device work (async dispatch would otherwise let the
        # window close early)
        _block_on(out)
    wall_s = time.perf_counter() - t0

    try:
        dev = jax.devices()[0]
        device_kind, backend = dev.device_kind, dev.platform
    except Exception:
        device_kind, backend = "", "unknown"
    spec = chip_spec(device_kind)
    meta = {
        "steps": steps,
        "step_ms": round(wall_s / max(steps, 1) * 1e3, 3),
        "flops_per_step": flops,
        "mfu_source": "cost_analysis" if flops else None,
        "device_kind": device_kind,
        "backend": backend,
        "peak_bf16_flops": spec.bf16_flops if spec else None,
        "chip": spec.name if spec else None,
    }
    if extra_meta:
        meta.update(extra_meta)
    with open(os.path.join(outdir, META_NAME), "w",
              encoding="utf-8") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
        f.write("\n")

    # publish the headline numbers as perf/* host counters: a capture
    # taken during a live Telemetry session lands in the run's JSONL
    # on its next flush (summarize's perf section).  Best-effort — a
    # torn capture must not fail the window that produced it.
    try:
        from apex_tpu.telemetry.profiler import report as _report
        rep = _report.build_report(outdir)
        if not rep.get("error"):
            _report.emit_perf_counters(rep)
    except Exception:
        pass
    return meta
