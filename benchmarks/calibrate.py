"""Read, on the chip, what a cell's limits are set from (not a run of
the benchmark; the driver never calls it).

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out chiprun_out/<file>.jsonl]

For every seed: the program's first steps (the job of the cell, built
and driven as ``run.py`` does) against the plain reference — the lower
readings.  For every control seed: the reference in the control's
precision (one step below the configuration's: fp8 for amp O2's
bfloat16), and the reference fed half of every batch, each against the
float32 reference — the upper readings.  One JSON line each; the limits
in ``limits/<cell>.json`` are set from them by hand, with room.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# libtpu would write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]

CONTROL_PRECISION = "fp8"
# the reference with operands rounded as the program rounds them: says
# how much of a program reading is rounding alone
PROGRAM_PRECISION = "bf16"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import check, run

    cell = run.Cell(args.workload, args.rehearse_cpu)
    from apex_tpu.platform import enable_compilation_cache
    devices, _ = run.devices_or_die(cell.chips, args.rehearse_cpu)
    enable_compilation_cache(min_compile_secs=0.0)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(os.path.join(ROOT, args.out), "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def numbers(got, ref):
        return {k: [v["value"], v["leaf"]]
                for k, v in check.compare(got, ref).items()}

    for seed in dict.fromkeys(seeds + controls):
        t0 = time.perf_counter()
        job = cell.job(seed, devices)
        try:
            program = run.first_steps(job) if seed in seeds else None
            batches = job.reference_batches(cell.reference_steps)
            spec = job.spec
        finally:
            job.close()
        del job
        gc.collect()
        jax.clear_caches()
        t1 = time.perf_counter()
        ref = cell.follow_reference(spec, seed, batches)
        t2 = time.perf_counter()
        if program is not None:
            emit({"kind": "program", "seed": seed, "losses": program["losses"],
                  "ref_losses": ref["losses"], "numbers": numbers(program, ref),
                  "program_s": t1 - t0, "reference_s": t2 - t1})
        if seed in controls:
            low = cell.follow_reference(spec, seed, batches,
                                        CONTROL_PRECISION)
            emit({"kind": "control_" + CONTROL_PRECISION, "seed": seed,
                  "numbers": numbers(low, ref)})
            same = cell.follow_reference(spec, seed, batches,
                                         PROGRAM_PRECISION)
            emit({"kind": "reference_" + PROGRAM_PRECISION, "seed": seed,
                  "numbers": numbers(same, ref)})
            half = cell.follow_reference(
                spec, seed,
                [tuple(a[:len(a) // 2] for a in b) for b in batches])
            emit({"kind": "fault_half_batch", "seed": seed,
                  "numbers": numbers(half, ref)})
    if out:
        out.close()


if __name__ == "__main__":
    main()
