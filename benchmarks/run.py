"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads`` (or of
``benchmarks/parked.json``, cells kept ready beside it); everything
that belongs to it is found by name: ``configs/<config>.json`` (as the
manifest says), ``traffic/<traffic>.json``, ``drivers/<driver>.py``,
``reference/<config>.py``, ``limits/<cell>.json``, and for each metric
``metrics/<metric>.json`` with its ``readers/<reader>.py``.  Adding a
cell, a configuration or a metric adds files; nothing here names one.

A run: set-up (weights and inputs from the seed, the cell's programs
warmed by the job's first steps, whose losses and optimizer state the
comparison reads), then the window (the same job, for ``--seconds``,
whole steps), then the memory peak, then — the program's state freed —
the plain reference over the same first steps and the comparison that
decides ``correct``.  The last line of standard output is the result.

Without a TPU whose kind is in ``counts.PEAKS`` the run fails; the one
way onto the CPU is ``--rehearse-cpu``, which runs the configuration's
``rehearsal`` sizes and says ``cpu`` in its last line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()             # process start, for setup_s

import argparse                       # noqa: E402
import gc                             # noqa: E402
import importlib                      # noqa: E402
import json                           # noqa: E402
import collections                    # noqa: E402
import os                             # noqa: E402
import re                             # noqa: E402
import shutil                         # noqa: E402
import sys                            # noqa: E402
import types                          # noqa: E402

# libtpu would write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the checkout's root on the path, this directory off it: its modules
# are ``benchmarks.<name>``, and none may shadow the standard library's
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]

FIRST_STEPS = 3                       # set-up's steps; the reference follows them
IN_FLIGHT = 2


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def flat(tree):
    """Nested dict of numbers -> {'a/b/c': float}."""
    out = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], prefix + (str(k),))
        else:
            out["/".join(prefix)] = float(node)
    walk(tree, ())
    return out


def read_metrics(entries, ctx):
    """name -> {"value", "unit"} for every entry whose reader finds
    something to read; one that finds nothing is left out, with a line
    on standard error, never reported as 0."""
    out = {}
    for m in entries:
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("params", {}))
        if value is None:
            say(f"metric {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def devices_or_die(chips, rehearse):
    import jax

    from benchmarks import counts
    devices = jax.devices()
    d0 = devices[0]
    if rehearse:
        if d0.platform != "cpu":
            raise SystemExit("--rehearse-cpu is for the CPU backend "
                             "(set JAX_PLATFORMS=cpu)")
        peaks = None
    else:
        if d0.platform != "tpu":
            raise SystemExit(
                f"no accelerator: jax found {d0.platform!r}; the benchmark "
                "measures on a TPU only (--rehearse-cpu rehearses)")
        peaks = counts.peaks(d0.device_kind)
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax found "
                         f"{len(devices)}")
    return devices, peaks


def run_window(job, seconds, first, trace_dir, trace_steps):
    """Drive ``job.step`` from step number ``first`` for ``seconds``:
    at most IN_FLIGHT steps in flight, the clock read at step
    boundaries, the window closed by waiting for everything.  With
    ``trace_dir`` the profiler runs over ``trace_steps`` steps from the
    third boundary on.  -> (window seconds, steps)."""
    import jax

    trace_from = IN_FLIGHT if trace_dir else None
    tracing = traced = False
    t0 = time.perf_counter()
    n = 0
    while True:
        if n >= IN_FLIGHT:
            job.wait(first + n - IN_FLIGHT)
        if trace_dir and not traced:
            if not tracing and n == trace_from:
                job.drain()
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                # level 1 keeps TraceAnnotation spans and drops the
                # runtime's per-chunk events (millions in an input copy)
                options.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
                tracing = job.spans.recording = True
            elif tracing and n == trace_from + trace_steps:
                job.drain()
                jax.profiler.stop_trace()
                tracing = job.spans.recording = False
                traced = True
        if time.perf_counter() - t0 >= seconds and not tracing \
                and (traced or not trace_dir):
            break
        job.step(first + n)
        n += 1
    job.drain()
    return time.perf_counter() - t0, n


def first_steps(job):
    """Drive the job's first steps through the window's own call and
    return what the comparison reads of them: each loss, the first
    gradient as the optimizer got it (from its state after one step)
    and the parameters' change after the last, as norms by leaf under
    the reference's names."""
    import jax

    for i in range(FIRST_STEPS):
        job.step(i)
        if i == 0:
            grad1 = job.first_update_norms()
    job.drain()
    got = jax.device_get({"losses": job.losses[:FIRST_STEPS],
                          "grad1": grad1, "change": job.change_norms()})
    return {"losses": [float(x) for x in got["losses"]],
            "grad1": {job.reference_name(k): v
                      for k, v in flat(got["grad1"]).items()},
            "change": {job.reference_name(k): v
                       for k, v in flat(got["change"]).items()}}


class Cell:
    """One workload of BENCHMARK.json with every file it names, found
    by name; ``rehearse`` swaps in the rehearsal's sizes and limits."""

    def __init__(self, workload, rehearse=False):
        self.manifest = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            # a cell kept ready beside the manifest (parked.json)
            parked = load_json(HERE, "parked.json")
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                self.manifest[key] = self.manifest[key] + parked[key]
            cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"or parked.json; there are {sorted(cells)}")
        self.entry = cells[workload]
        self.name, self.chips = workload, self.entry["chips"]
        self.config = next(c for c in self.manifest["configs"]
                           if c["name"] == self.entry["config"])["name"]
        config = load_json(ROOT, next(
            c["file"] for c in self.manifest["configs"]
            if c["name"] == self.config))
        self.traffic = load_json(HERE, "traffic",
                                 self.entry["traffic"] + ".json")
        self.limits = load_json(HERE, "limits", workload + ".json")
        # the sizes are the file's top-level values; groups hold the rest
        self.sizes = {k: v for k, v in config.items()
                      if not isinstance(v, dict)}
        self.optimizer = dict(config["optimizer"])
        if rehearse:
            self.sizes.update(config["rehearsal"])
            self.traffic.update(self.traffic["rehearsal"])
            self.limits["limits"] = self.limits.get("rehearsal_limits",
                                                    self.limits["limits"])
        if self.traffic["chips"] != self.chips:
            raise SystemExit(f"traffic {self.entry['traffic']!r} is for "
                             f"{self.traffic['chips']} chips, the cell says "
                             f"{self.chips}")
        self.reference_steps = self.limits.get("reference_steps",
                                               FIRST_STEPS)

    @property
    def reference(self):
        return importlib.import_module("benchmarks.reference." + self.config)

    def metrics(self, section):
        """Entries of ``end_to_end`` / ``per_layer`` this cell reports."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def job(self, seed, devices, traffic=None):
        """The cell's job, built by its driver (this imports the
        program: a checkout without it fails here)."""
        driver = importlib.import_module(
            "benchmarks.drivers." + self.sizes["driver"])
        return driver.Job(root=ROOT, sizes=self.sizes,
                          optimizer=self.optimizer,
                          traffic=traffic or self.traffic,
                          reference=self.reference, seed=seed,
                          devices=list(devices)[:self.chips])

    def follow_reference(self, spec, seed, batches, precision="f32"):
        """The plain reference over ``batches`` from the seed's weights,
        in the comparison's flat form."""
        from benchmarks import weights

        ref = self.reference.follow(weights.make(spec, seed), batches,
                                    self.sizes, self.optimizer, precision)
        return {"losses": ref["losses"], "grad1": flat(ref["grad1"]),
                "change": flat(ref["change"])}


def kernel_census(hlo_text):
    """Mosaic kernels of one compiled program: name -> count (copy of
    chip_smoke.py's)."""
    census = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ",
                     line)
        census[m.group(1) if m else "?"] += 1
    return dict(census)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the configuration's rehearsal sizes on the "
                         "CPU backend; no number of such a run is a "
                         "device metric")
    args = ap.parse_args(argv)
    t_start = _T0 if argv is None else time.perf_counter()

    cell = Cell(args.workload, args.rehearse_cpu)

    import jax

    from apex_tpu.platform import enable_compilation_cache
    from apex_tpu.telemetry.retrace import (BACKEND_COMPILE_EVENT,
                                            RetraceCounter)

    from benchmarks import check
    from benchmarks import traceread as tracelib
    from benchmarks.jobs import SPAN_NAMES

    devices, peaks = devices_or_die(cell.chips, args.rehearse_cpu)
    used = list(devices)[:cell.chips]
    enable_compilation_cache(min_compile_secs=0.0)
    retrace = RetraceCounter()
    retrace.install()
    job = cell.job(args.seed, devices)
    trace_dir = None
    try:
        # ---- set-up: the first steps, on the object the window gets ----
        program = first_steps(job)
        compiles0 = retrace.events[BACKEND_COMPILE_EVENT]
        setup_s = time.perf_counter() - t_start

        # ---- the window ------------------------------------------------
        if args.trace:
            trace_dir = os.path.join(ROOT, ".bench_trace",
                                     "%s.%d" % (cell.name, args.seed))
            shutil.rmtree(trace_dir, ignore_errors=True)
        window_s, steps = run_window(job, args.seconds, FIRST_STEPS,
                                     trace_dir, cell.traffic["trace_steps"])
        compiles = retrace.events[BACKEND_COMPILE_EVENT] - compiles0
        stats = [d.memory_stats() or {} for d in used]
        # program scratch is not in ``bytes_in_use`` on this backend: a
        # loaded executable's temporaries stand under ``bytes_reserved``
        # (PERF.md section 7, PR 24), so the chip's peak is the two together
        fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0))
        peak_bytes = (fullest.get("peak_bytes_in_use", 0)
                      + fullest.get("peak_bytes_reserved", 0))
        say("memory_stats:", json.dumps(fullest))
        failed = job.failed_steps(FIRST_STEPS)

        ctx = types.SimpleNamespace(
            window_s=window_s, steps=steps, setup_s=setup_s,
            units_per_step=job.units_per_step, peak_bytes=peak_bytes,
            chips=cell.chips, peaks=peaks, counts=job.counts,
            programs=job.programs, first_program=job.first_program,
            counters={"compiles_in_window": compiles},
            span_rows=list(job.spans.rows), trace=None, steady=None)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak_bytes,
                  "peak_bytes_in_use": fullest.get("peak_bytes_in_use", 0),
                  "peak_bytes_reserved": fullest.get("peak_bytes_reserved", 0)}
        breakdown = None
        if args.trace:
            try:
                census = {role: kernel_census(c.as_text())
                          for role, c in job.compiled_programs().items()}
                say("kernel census:", json.dumps(census))
            except Exception as e:                # a print, not a metric
                say(f"kernel census failed: {type(e).__name__}: {e}")
            ctx.trace = tracelib.load_xplane(
                tracelib.find_xplane(trace_dir), SPAN_NAMES)
            ctx.steady = tracelib.steady_window(
                ctx.trace, job.programs[job.first_program],
                skip=2 if cell.traffic["trace_steps"] > 6 else 0)
            if ctx.steady is None:
                say("trace: fewer than two executions of the step's first "
                    "program; no device metric can be read")
            else:
                device["busy_s"] = tracelib.busy_seconds(ctx.trace,
                                                         ctx.steady)
                device["window_s"] = ctx.steady.seconds
                breakdown = {
                    "device_ops": tracelib.top_ops(ctx.trace, ctx.steady),
                    "idle_gaps": tracelib.idle_gaps(ctx.trace, ctx.steady)}
        metrics = read_metrics(
            cell.metrics("per_layer" if args.trace else "end_to_end"), ctx)

        # ---- the comparison, once the program's state is gone ----------
        batches = job.reference_batches(cell.reference_steps)
        spec = job.spec
    finally:
        job.close()
        retrace.uninstall()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del job, ctx
    gc.collect()
    # unload the program's executables too: each keeps its scratch
    # reserved on the chip for as long as it is loaded
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = cell.follow_reference(spec, args.seed, batches)
    say(f"reference: {cell.reference_steps} steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    numbers = check.compare(program, ref)
    correct, rows = check.decide(numbers, cell.limits["limits"])
    correct = correct and failed == 0
    spare = {name: got["value"] for name, got in numbers.items()
             if name not in cell.limits["limits"]}
    say("read but not held to a limit:", json.dumps(spare))
    say(f"steps attempted {steps}, failed {failed}")
    for r in rows:
        say(f"compared {r['name']} = {r['value']:.6g} limit {r['limit']:g} "
            f"{'ok' if r['ok'] else 'FAILED'}"
            + (f" (worst leaf {r['leaf']})" if r["leaf"] else ""))

    result = {"correct": bool(correct), "attempted": steps,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["not_compared"] = spare
    result["compared"] = {
        r["name"]: {"value": r["value"] if r["value"] == r["value"]
                    and abs(r["value"]) != float("inf") else None,
                    "limit": r["limit"]} for r in rows}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
