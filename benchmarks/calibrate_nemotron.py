"""Read, on the chip, what the limits of a cell whose reference names
its model's own faults (``FAULTS``: name -> the sizes it changes) are
set from (not a run of the benchmark; the driver never calls it):

    python benchmarks/calibrate_nemotron.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--kinds ...] [--out <file>.jsonl]

For every seed: the program's first steps (the job of the cell, built
and driven as ``run.py`` does) against the plain reference — the lower
readings.  For every control seed, each against the float32 reference
— the upper readings, every one of which has to fail a held limit:

- ``control_fp8``: the reference with matmul operands rounded to fp8;
- each of the reference's ``FAULTS`` (the Mamba-2 cell's: the decay
  dropped, the D skip dropped, the gated norm over all channels, relu
  for relu^2, B and C tiled over the heads instead of repeated);
- ``fault_unchanged_state``: an optimizer step that returns its state
  unchanged (every norm the comparison reads is 0).

One JSON line each; the limits in ``limits/<cell>.json`` are set from
them by hand, with room.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") not in (HERE, ROOT)]

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--kinds", default="",
                    help="comma-separated controls and faults to read "
                         "(default: all)")
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
    kinds = [k for k in args.kinds.split(",") if k]
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

    def follow(spec, seed, batches, precision="f32", **sizes):
        kept = dict(cell.sizes)
        cell.sizes.update(sizes)
        try:
            return cell.follow_reference(spec, seed, batches, precision)
        finally:
            cell.sizes = kept

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
        ref = follow(spec, seed, batches)
        t2 = time.perf_counter()
        if program is not None:
            emit({"kind": "program", "seed": seed, "losses": program["losses"],
                  "ref_losses": ref["losses"], "numbers": numbers(program, ref),
                  "program_s": t1 - t0, "reference_s": t2 - t1})
        if seed not in controls:
            continue
        reads = {"control_fp8": lambda: follow(spec, seed, batches, "fp8")}
        for kind, change in cell.reference.FAULTS.items():
            reads[kind] = (lambda change=change:
                           follow(spec, seed, batches, **change))
        reads["fault_unchanged_state"] = lambda: {
            "losses": ref["losses"],
            "grad1": dict.fromkeys(ref["grad1"], 0.0),
            "change": dict.fromkeys(ref["change"], 0.0)}
        for kind, read in reads.items():
            if kinds and kind not in kinds:
                continue
            emit({"kind": kind, "seed": seed,
                  "numbers": numbers(read(), ref)})
    if out:
        out.close()


if __name__ == "__main__":
    main()
