"""Capture a jax.profiler trace of the north-star training step
(ResNet-50 amp O2 + FusedSGD — BASELINE.md) for the step-time
breakdown in docs/perf.md.

    python tools/profile_step.py [--outdir chiprun_out/trace]

Writes a TensorBoard/XProf trace directory and prints one JSON line
with the measured step time (and MFU when the chip is recognized).
It traces on the TPU or exits non-zero; only the process that holds
the chip can trace it, so this tool imports jax and captures
in-process and starts no child.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def capture_trace(outdir: str, jax) -> dict:
    """Trace the north-star training step at the tracked b128 config
    (a short 20-step leg — NOT bench.py's full b128/b256 sweep, whose
    reported number may come from a different batch; compare this
    summary's step_ms against the matching batch_sweep entry) and
    return the summary dict.

    The capture body is apex_tpu.telemetry.profiler.capture — ONE
    code path for device-only tracing (host/python tracers off: a
    default-options capture drowns a few hundred device ops in ~1M
    host python events) shared with profile_window and the
    observatory."""
    import jax.numpy as jnp

    import bench
    from apex_tpu.telemetry.profiler import capture

    t0 = time.perf_counter()
    with capture.trace(outdir):
        r = bench._resnet50_one_batch(jax, jnp, 128, 224, 20)
    out = {"trace_dir": outdir,
           "backend": jax.default_backend(),
           "wall_s": round(time.perf_counter() - t0, 1),
           "resnet50_step_ms": round(r["step_ms"], 2),
           "imgs_per_sec": round(r["imgs_per_sec"], 1)}
    if r.get("mfu") is not None:
        out["mfu"] = r["mfu"]
    out["top_device_ops"] = summarize_device_ops(outdir)
    return out


def summarize_device_ops(outdir: str, top: int = 12):
    """Delegates to the package home of the parser
    (apex_tpu.pyprof.prof — the reference's pyprof/prof kernel-parse
    half lives in the PACKAGE, not the tools dir)."""
    from apex_tpu.pyprof.prof import summarize_device_ops as impl
    return impl(outdir, top=top)


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir",
                    default=os.path.join(root, "chiprun_out", "trace"))
    args = ap.parse_args()

    from apex_tpu.platform import enable_compilation_cache

    import jax
    enable_compilation_cache()
    if jax.default_backend() != "tpu":
        print(f"profile_step.py: backend is {jax.default_backend()!r}, "
              "not 'tpu' — nothing to trace", file=sys.stderr)
        return 2

    out = capture_trace(args.outdir, jax)
    print(json.dumps(out))
    print(f"# view: tensorboard --logdir {args.outdir}  (Profile tab)",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
