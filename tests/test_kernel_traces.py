"""``setup_kernel_traces``: the hybrid cell reports it, its reader takes
the process account's ``kernel_traces`` up to the window's first step,
and a program whose account has no such field (one from before the
kernel programs were shared) gives nothing.  On the CPU; no number here
is a device metric."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.telemetry import retrace  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.readers import startup  # noqa: E402

CELL = "kimi_linear_48b_a3b_adamw.hybrid_s4096"


def test_the_hybrid_cell_reports_its_kernel_traces():
    reported = {m["name"] for m in run.Cell(CELL).metrics("per_layer")}
    assert "setup_kernel_traces" in reported
    entry, = [m for m in run.load_json(ROOT, "BENCHMARK.json")["per_layer"]
              if m["name"] == "setup_kernel_traces"]
    assert entry == {
        "name": "setup_kernel_traces", "unit": "count", "better": "lower",
        "source": "program_counter",
        "layer": "dispatch (ops/_dispatch.py, jit caches)",
        "moves": "setup_s", "workloads": [CELL]}
    assert run.load_json(ROOT, "benchmarks", "metrics",
                         "setup_kernel_traces.json") == {
        "reader": "startup",
        "params": {"before_step": run.FIRST_STEPS, "field": "kernel_traces"}}


@pytest.fixture
def account(monkeypatch):
    acct = retrace.ProcessAccount()
    acct.open = True
    monkeypatch.setattr(retrace, "_PROCESS", acct)
    monkeypatch.setattr(retrace, "process", lambda: acct)
    monkeypatch.setattr(startup, "_run_is_the_process", lambda: True)
    return acct


def read(ctx=None):
    params = run.load_json(ROOT, "benchmarks", "metrics",
                           "setup_kernel_traces.json")["params"]
    return startup.read(ctx or types.SimpleNamespace(), **params)


def test_kernel_traces_count_up_to_the_windows_first_step(account):
    for t in (101.0, 102.0, 103.0):
        account.kernel_marks.append(t)
    account.marks = [110.0, 111.0, 112.0]
    assert read() is None                       # the window never began
    account.marks.append(113.0)
    account.kernel_marks.append(150.0)          # the kernel census, after
    assert read() == 3
    assert account.summary()["kernel_traces"] == 4
    # the report reads up to the first quiet step, the window's first here
    assert "3 kernel programs traced" in account.report()


def test_kernel_trace_stamps_only_while_the_account_is_open(account):
    retrace.kernel_trace()
    account.close()
    retrace.kernel_trace()
    assert len(account.kernel_marks) == 1


def test_an_account_without_the_field_gives_nothing(account, monkeypatch):
    account.marks = [1.0, 2.0, 3.0, 4.0]
    monkeypatch.setattr(account, "until_step",
                        lambda k: {"wall_s": 4.0, "trace_s": 1.0})
    assert read() is None
