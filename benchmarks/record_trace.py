"""Record a few steps of a cell under the profiler and keep what a
person needs to read the trace by hand (not a run of the benchmark; the
driver never calls it).

    python benchmarks/record_trace.py --workload <cell> --steps 4 --out chiprun_out/<stem>

writes ``<stem>.planes.json`` (every plane and line with its event count
and first names: look here first on a new device or JAX version) and
``<stem>.trace.json`` (the module and op lines of each chip and the
harness's host spans, as ``traceread.Trace`` keeps them, cut to the
steady window: the recorded trace beside the tests was made so).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

# libtpu would write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from apex_tpu.platform import enable_compilation_cache
    from benchmarks import run, traceread
    from benchmarks.jobs import SPAN_NAMES

    cell = run.Cell(args.workload, args.rehearse_cpu)
    devices, _ = run.devices_or_die(cell.chips, args.rehearse_cpu)
    enable_compilation_cache(min_compile_secs=0.0)
    job = cell.job(args.seed, devices)
    logdir = os.path.join(ROOT, ".bench_trace", "record")
    shutil.rmtree(logdir, ignore_errors=True)
    try:
        for i in range(run.FIRST_STEPS):
            job.step(i)
        job.drain()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=options)
        for i in range(args.steps):
            if i >= run.IN_FLIGHT:
                job.wait(run.FIRST_STEPS + i - run.IN_FLIGHT)
            job.step(run.FIRST_STEPS + i)
        job.drain()
        jax.profiler.stop_trace()
    finally:
        job.close()
    path = traceread.find_xplane(logdir)
    stem = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(stem), exist_ok=True)
    with open(stem + ".planes.json", "w") as f:
        json.dump(traceread.describe_xplane(path), f, indent=1)
    trace = traceread.load_xplane(path, SPAN_NAMES)
    window = traceread.steady_window(
        trace, job.programs[job.first_program])
    if window is not None:
        for lines in trace.devices.values():
            for name in lines:
                lines[name] = traceread.clipped(lines[name], window)
        trace.host = traceread.clipped(trace.host, window)
    traceread.save_json(trace, stem + ".trace.json")
    print(json.dumps({"xplane_bytes": os.path.getsize(path),
                      "window": None if window is None else
                      [window.start, window.end, window.steps]}))
    shutil.rmtree(logdir, ignore_errors=True)


if __name__ == "__main__":
    main()
