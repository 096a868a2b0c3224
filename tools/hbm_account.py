"""Where a benchmark cell's device bytes stand, stage by stage.

    chiprun --chips 1 -- python tools/hbm_account.py \
        --workload ouro_2p6b_adamw.pretrain_s4096 [--seed 7] [--steps 6]

Builds the cell's job as ``benchmarks/run.py`` does and drives its
first steps, reading ``memory_stats()`` (``bytes_in_use``, the lifetime
``peak_bytes_in_use``, ``bytes_reserved``, ``peak_bytes_reserved``) and
``jax.live_arrays()`` after every stage that allocates: the seeded
weights, ``amp.initialize``, each pack and state call inside the fused
optimizer's constructor, each launch inside ``step()``.  The lifetime
peak is monotonic, so the stage in which it rises is the stage that set
it.  One JSON line a stage on standard output, the same lines in
``chiprun_out/hbm_account.<workload>.jsonl`` (``.cpu.jsonl`` from a
rehearsal); bytes a parameter beside every reading.  On a backend without ``memory_stats`` (the CPU) only
the live arrays are read, and no line is a device number.

One process: imports jax and measures in-process.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Account:
    """The readings, in order; ``n_params`` turns bytes into bytes a
    parameter once the job knows it."""

    def __init__(self, device, top):
        self.device, self.top = device, top
        self.rows = []
        self._wrapped = []

    def probe(self, stage):
        import jax

        stats = self.device.memory_stats() or {}
        groups = collections.Counter()
        counts = collections.Counter()
        for a in jax.live_arrays():
            key = (str(a.dtype), tuple(a.shape))
            groups[key] += a.nbytes
            counts[key] += 1
        self.rows.append({
            "stage": stage,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_reserved": stats.get("bytes_reserved"),
            "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
            "live_arrays_bytes": sum(groups.values()),
            "live_groups": [
                {"dtype": k[0], "shape": list(k[1]), "count": counts[k],
                 "bytes": b} for k, b in groups.most_common(self.top)],
        })

    def after(self, owner, name, stage):
        """Probe after every call of ``owner.name`` (skipped where the
        tree under test has no such attribute)."""
        fn = getattr(owner, name, None)
        if fn is None:
            return

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            out = fn(*args, **kwargs)
            self.probe(stage)
            return out
        self._wrapped.append((owner, name, fn))
        setattr(owner, name, wrapped)

    def restore(self):
        for owner, name, fn in reversed(self._wrapped):
            setattr(owner, name, fn)
        self._wrapped = []


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--top", type=int, default=8,
                    help="live-array groups kept per stage")
    ap.add_argument("--hlo", action="store_true",
                    help="also write the optimizer step's compiled HLO "
                         "and count its aliases and bucket-sized copies")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from apex_tpu import amp
    from apex_tpu.multi_tensor_apply.packer import BucketPlan
    from apex_tpu.optimizers import _base
    from apex_tpu.platform import enable_compilation_cache
    from benchmarks import run as bench
    from benchmarks import weights

    cell = bench.Cell(args.workload, args.rehearse_cpu)
    devices, _ = bench.devices_or_die(cell.chips, args.rehearse_cpu)
    enable_compilation_cache(min_compile_secs=0.0)
    acct = Account(devices[0], args.top)

    acct.after(weights, "make", "weights.make (float32 tree)")
    acct.after(amp, "initialize", "amp.initialize")
    acct.after(BucketPlan, "pack_model", "ctor: pack_model")
    acct.after(BucketPlan, "pack_work", "ctor: pack_work")
    acct.after(BucketPlan, "pack_state_field", "ctor: pack_state_field")
    base = _base.FusedOptimizerBase
    for cls in base.__subclasses__():
        if "init_state" in cls.__dict__:
            acct.after(cls, "init_state", "ctor: init_state (per leaf)")
    acct.after(base, "init_state_packed", "ctor: init_state_packed")
    acct.after(base, "__init__", "optimizer built")

    acct.probe("start")
    job = cell.job(args.seed, devices)
    acct.probe("job built (driver dropped its trees)")
    opt = job.opt
    acct.after(opt, "_jit_step", "step: optimizer program launched")
    acct.after(opt, "_unpack_model_jit", "step: unpack launched")
    if hasattr(job, "jstep"):
        acct.after(job, "jstep", "step: fwd_bwd launched")
    for i in range(args.steps):
        if i >= bench.IN_FLIGHT:
            job.wait(i - bench.IN_FLIGHT)
        job.step(i)
        acct.probe(f"step {i} launched")
        if i == 0:
            job.first_update_norms()
        if i == bench.FIRST_STEPS - 1:
            job.drain()
            jax.block_until_ready(job.change_norms())
            acct.probe("first steps drained, change_norms read")
    job.drain()
    acct.probe("drained")

    n = job.counts["n_params"]
    keys = ("bytes_in_use", "peak_bytes_in_use", "live_arrays_bytes")
    out_dir = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "hbm_account.%s%s.jsonl" % (
        args.workload, ".cpu" if args.rehearse_cpu else ""))
    with open(path, "w") as f:
        for row in acct.rows:
            row["per_param"] = {k: round(row[k] / n, 3) for k in keys
                                if row[k] is not None}
            f.write(json.dumps(row) + "\n")
            brief = {k: v for k, v in row.items() if k != "live_groups"}
            print(json.dumps(brief), flush=True)
    peak = max(acct.rows, key=lambda r: r["peak_bytes_in_use"] or 0)
    first = next(r for r in acct.rows
                 if r["peak_bytes_in_use"] == peak["peak_bytes_in_use"])
    print(json.dumps({
        "workload": args.workload, "n_params": n,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind},
        "lifetime_peak_first_seen_at": first["stage"],
        "live_groups_there": first["live_groups"]}), flush=True)
    acct.restore()
    if args.hlo:
        text = job.compiled_programs()["optimizer"].as_text()
        with open(path[:-len("jsonl")] + "optimizer.hlo.txt", "w") as f:
            f.write(text)
        print(json.dumps(hlo_summary(text, n)), flush=True)
    job.close()


def hlo_summary(text, n_params):
    """Of one compiled step program: how many arguments it aliases to
    outputs, and its ``copy`` instructions of at least a thousandth of
    the parameters (a bucket's worth), by shape."""
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    copies = collections.Counter()
    for m in re.finditer(r"= (\w+)\[([\d,]*)\][^ ]* copy\(", text):
        dims = [int(d) for d in m.group(2).split(",") if d]
        size = 1
        for d in dims:
            size *= d
        if size * 1000 >= n_params:
            copies[f"{m.group(1)}[{m.group(2)}]"] += 1
    return {"aliased_arguments": alias.group(1).count("-alias)")
            if alias else 0,
            "bucket_sized_copies": dict(copies)}


if __name__ == "__main__":
    main()
