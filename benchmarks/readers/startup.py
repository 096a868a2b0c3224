"""What set-up was made of, from the program's own account of it
(``apex_tpu.telemetry.retrace.process()``, open from the import of
``apex_tpu`` on): of everything that ended before optimizer step
``before_step`` (from 0) began — the window's first step, so nothing a
run compiles after its window (the kernel census of a traced run) is in
it — either ``field`` of the account's summary (``trace_s``,
``lower_s``, ``backend_s``: wall seconds of jax's trace / lowering /
backend-compile events that no other such event enclosed;
``cache_misses``: programs compiled and written to the persistent
cache, 0 on a warm run), or with ``phase`` the wall seconds inside that
phase span of the library (``apex/optim/init``) LESS the trace,
lowering and backend seconds inside it, which the three fields already
hold: the parts are disjoint, so their sum stays under ``setup_s`` on a
cold run too (where the constructor's programs compile inside it).

The account is the PROCESS's: its first steps are this run's only where
the run is the process (``python benchmarks/run.py ...``, as the driver
runs a cell).  Called from another program (a test's run in its own
process, a calibration over several seeds) the first steps may be
another job's, and the reader gives nothing.  So does a program without
the account (any commit before PR 35), an account that closed before
that step, and a phase that never ran."""

import os
import sys

from apex_tpu.telemetry import retrace

RUN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))), "run.py")


def _run_is_the_process():
    main = getattr(sys.modules.get("__main__"), "__file__", None)
    return main is not None and os.path.realpath(main) == RUN


def read(ctx, before_step, field=None, phase=None):
    process = getattr(retrace, "process", None)
    if process is None or not _run_is_the_process():
        return None
    summary = process().until_step(before_step)
    if summary is None:
        return None
    if phase is not None:
        row = summary["phases"].get(phase)
        return None if row is None else row["own_s"]
    return summary.get(field)
