"""What a benchmark cell's ``setup_s`` was made of, program by program.

    chiprun --chips 1 -- python tools/setup_account.py \
        --workload ouro_2p6b_adamw.pretrain_s4096 --seed 7 --seconds 20 --trace 1

Runs ``benchmarks/run.py`` with the same arguments in this process, AS
the process's main program (so the ``setup_*`` metrics, which read the
process's account, are in its result line as in the driver's run), and
after it prints what the five metrics leave out, one JSON line on
standard output after the run's own and the same in
``chiprun_out/setup_account.<workload>.<seed>.json``: the run's
``setup_s`` (a traced run's result line has none), the parts before the
window's first step with the remainder, each phase with the compile
seconds inside it, where the remainder lies (before the account, to the
first program, the first steps), the ten slowest programs, what the account kept
AFTER that step (a short window leaves the account open for the kernel
census's two compilations: they must be there and in no part), and the
account's own report on standard error.  A tree without the account
(before PR 35) runs the cell and prints ``"account": null``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _later(before, everything):
    """program -> what its row gained after the window's first step
    began (seconds and traces; nothing for a row that did not move)."""
    out = {}
    for name, row in everything.items():
        was = before.get(name, {})
        gained = {k: v - was.get(k, 0) for k, v in row.items()
                  if k != "cache" and v != was.get(k, 0)}
        if gained:
            out[name] = gained
    return out


def main():
    path = os.path.join(_ROOT, "benchmarks", "run.py")
    spec = importlib.util.spec_from_file_location("benchmarks_run", path)
    run = importlib.util.module_from_spec(spec)
    sys.modules["__main__"] = run        # the run IS the process
    spec.loader.exec_module(run)         # its clock starts here

    seen = {}
    read_metrics = run.read_metrics

    def keeping_setup_s(entries, ctx):
        seen["setup_s"] = ctx.setup_s
        return read_metrics(entries, ctx)

    run.read_metrics = keeping_setup_s
    run.main()

    from apex_tpu.telemetry import retrace
    out = {"workload": sys.argv[sys.argv.index("--workload") + 1],
           "setup_s": seen.get("setup_s"), "account": None}
    process = getattr(retrace, "process", None)
    if process is not None:
        account = process()
        before = account.until_step(run.FIRST_STEPS)
        everything = account.summary()
        if before is not None:
            parts = {k: before[k] for k in ("trace_s", "lower_s",
                                            "backend_s")}
            parts["optim_init_s"] = before["phases"].get(
                "apex/optim/init", {}).get("own_s", 0.0)
            slowest = retrace._slowest(before["programs"], 10)
            out["account"] = {
                "wall_s": before["wall_s"], **parts,
                "remainder_s": seen.get("setup_s", 0.0) - sum(parts.values()),
                "counts": {k: before[k] for k in before
                           if k.endswith("_n") or k.startswith("cache_")},
                "phases": before["phases"], "slowest": dict(slowest),
                # where the remainder lies: before the account began
                # (python and jax imported), from there to the first
                # program (the rest of the imports, the backend's
                # start), and from the first step's beginning on
                "before_account_s": seen.get("setup_s", 0.0) - before["wall_s"],
                "to_first_program_s": (account.spans[0].start
                                       - account.started),
                "to_first_step_s": account.marks[0] - account.started,
                "first_steps_s": (account.marks[run.FIRST_STEPS]
                                  - account.marks[0]),
                "events": dict(account.events),
                "first_quiet_step": account.first_quiet_step(),
                "steps_marked": len(account.marks), "open": account.open,
                "after_window_began": _later(before["programs"],
                                             everything["programs"])}
        print(account.report(), file=sys.stderr, flush=True)
    line = json.dumps(out)
    print(line, flush=True)
    seed = sys.argv[sys.argv.index("--seed") + 1]
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "setup_account.%s.%s.json"
                           % (out["workload"], seed)), "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
