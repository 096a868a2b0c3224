"""The per-layer metrics under ``setup_s`` (PR 35) and ``moe_fill_ratio``:
their readers on hand-built input, what they give where there is
nothing to read, and one traced rehearsal in a process of its own, the
only place where the process's account is the run's.  On the CPU; no
number here is a device metric.
"""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.telemetry import retrace  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.readers import job_count_ratio, startup  # noqa: E402

SETUP = ("setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_cache_misses", "setup_optim_init_s")
LOOPED = "ouro_2p6b_adamw.pretrain_s4096"


def spec(name):
    return run.load_json(ROOT, "benchmarks", "metrics", name + ".json")


def read(name):
    return startup.read(types.SimpleNamespace(), **spec(name)["params"])


@pytest.fixture
def account(monkeypatch):
    """A process account fed by hand: three steps of set-up, the
    window's first step at 130 s, a compilation after it."""
    acct = retrace.ProcessAccount()
    acct.started = 100.0
    event = {v: k for k, v in retrace.KINDS.items()}
    for kind, name, start, end in (
            ("trace", "convert_element_type", 101.0, 101.5),
            ("backend", "jit(init_state_packed)", 103.0, 104.0),
            ("trace", "step", 110.0, 112.0),
            ("lower", "jit(step)", 112.0, 115.0)):
        acct._on_time_span(event[kind], start, end, fun_name=name)
    acct._on_event(retrace.CACHE_MISS_EVENT)
    acct._on_time_span(event["backend"], 115.0, 124.0, fun_name="jit(step)")
    acct.add_phase("apex/optim/init", 102.5, 104.75)
    acct.marks = [125.0, 127.0, 128.0, 130.0]
    # the kernel census of a traced run: after the window
    acct._on_time_span(event["lower"], 160.0, 163.0, fun_name="jit(step)")
    acct._on_time_span(event["backend"], 163.0, 170.0, fun_name="jit(step)")
    monkeypatch.setattr(retrace, "_PROCESS", acct)
    monkeypatch.setattr(retrace, "process", lambda: acct)
    monkeypatch.setattr(startup, "_run_is_the_process", lambda: True)
    return acct


def test_the_readers_sum_what_ended_before_the_windows_first_step(account):
    assert read("setup_trace_s") == pytest.approx(2.5)
    assert read("setup_lower_s") == pytest.approx(3.0)      # not the census's
    assert read("setup_backend_s") == pytest.approx(10.0)
    assert read("setup_cache_misses") == 1
    # 2.25 s inside the phase, 1 s of them the backend's: the parts
    # are disjoint
    assert read("setup_optim_init_s") == pytest.approx(1.25)
    assert account.until_step(3)["phases"]["apex/optim/init"][
        "seconds"] == pytest.approx(2.25)
    whole = account.summary()
    assert whole["lower_s"] == pytest.approx(6.0)
    assert whole["backend_s"] == pytest.approx(17.0)


def test_the_readers_give_nothing_where_there_is_nothing_to_read(
        account, monkeypatch):
    account.marks = account.marks[:3]        # the window never began
    assert [read(name) for name in SETUP] == [None] * 5
    account.marks.append(130.0)
    account.phases.clear()                   # no fused optimizer was built
    assert read("setup_optim_init_s") is None
    assert read("setup_trace_s") == pytest.approx(2.5)
    # a run inside another program (these tests): the process's first
    # steps may be another job's
    monkeypatch.undo()
    monkeypatch.setattr(retrace, "process", lambda: account)
    assert not startup._run_is_the_process()
    assert [read(name) for name in SETUP] == [None] * 5
    # a program from before PR 35 has no account
    monkeypatch.setattr(startup, "_run_is_the_process", lambda: True)
    assert read("setup_trace_s") == pytest.approx(2.5)
    monkeypatch.delattr(retrace, "process")
    assert [read(name) for name in SETUP] == [None] * 5


@pytest.mark.parametrize("name", SETUP)
def test_setup_metrics_stop_at_the_windows_first_step(name):
    assert spec(name)["reader"] == "startup"
    assert spec(name)["params"]["before_step"] == run.FIRST_STEPS
    entry, = [m for m in run.load_json(ROOT, "BENCHMARK.json")["per_layer"]
              if m["name"] == name]
    assert entry["moves"] == "setup_s" and "workloads" not in entry


def test_fill_ratio_is_rows_routed_here_over_an_unbiased_routers():
    params = spec("moe_fill_ratio")["params"]
    ctx = types.SimpleNamespace(steady=object(), counts={
        "expert_tokens": [[512] * 8, [1024] * 4 + [2048] * 4],
        "expected_tokens_per_expert": 512.0})
    # layer 1 at the expected fill, layer 2 at three times it
    assert job_count_ratio.read(ctx, **params) == pytest.approx(2.0)
    # no steady device window to read it beside (a CPU rehearsal)
    ctx.steady = None
    assert job_count_ratio.read(ctx, **params) is None
    ctx.steady = object()
    del ctx.counts["expert_tokens"]
    assert job_count_ratio.read(ctx, **params) is None


def test_a_traced_rehearsal_of_its_own_reports_what_setup_was_made_of():
    """``tools/setup_account.py`` runs the cell as the process's main
    program: the five parts are in the result line, their sum under the
    run's ``setup_s``, and what the run compiled after its window (the
    kernel census, the reference) is in the account and in no part."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "setup_account.py"),
         "--workload", LOOPED, "--seed", str(2 ** 31 + 35), "--seconds",
         "0.5", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result, extra = [json.loads(line) for line in
                     done.stdout.strip().splitlines()[-2:]]
    assert result["correct"] is True
    got = {name: result["metrics"][name]["value"] for name in SETUP}
    assert result["metrics"]["setup_trace_s"]["unit"] == "s"
    assert min(got["setup_trace_s"], got["setup_lower_s"],
               got["setup_backend_s"], got["setup_optim_init_s"]) > 0
    assert got["setup_cache_misses"] == int(got["setup_cache_misses"]) >= 0
    parts = sum(v for k, v in got.items() if k != "setup_cache_misses")
    assert parts <= extra["setup_s"]
    account = extra["account"]
    assert account["remainder_s"] == pytest.approx(extra["setup_s"] - parts)
    assert account["wall_s"] <= extra["setup_s"]
    # a short window leaves the account open for what came after it
    assert account["open"] and account["steps_marked"] < retrace.MAX_STEPS
    later = account["after_window_began"]
    assert sum(row.get("backend_s", 0) for row in later.values()) > 0
    assert "set-up account" in done.stderr
