"""The performance observatory (apex_tpu.telemetry.profiler +
tools/perf_gate.py): trace parsing, attribution buckets, overlap math,
cost-model MFU, report rendering, perf counters through the session
JSONL, and the BENCH-trajectory regression gate — all CPU-only.

The checked-in fixture (tests/profiler_fixtures/) is hand-built so
every bucket is exactly computable; its README tabulates the math the
assertions below pin."""

import gzip
import importlib.util
import io
import json
import os
import shutil

import pytest

from apex_tpu.telemetry import profiler
from apex_tpu.telemetry.profiler import attribution, events

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(_ROOT, "tests", "profiler_fixtures")


def _load_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


perf_gate = _load_path("perf_gate",
                       os.path.join(_ROOT, "tools", "perf_gate.py"))


# ---------------------------------------------------------------------------
# parser


def test_fixture_parses_device_thread_only():
    evs = events.load_device_events(FIXTURE)
    # 8 device rows; the python host thread (frame + PjitFunction
    # range) is never device work
    assert len(evs) == 8
    assert {e.thread for e in evs} == {"XLA Ops"}
    assert all(e.hlo_module == "jit_train_step" for e in evs)
    names = [e.name for e in evs]
    assert "PjitFunction(train_step)" not in names
    # rows come back time-sorted with end_us derived
    assert names[0] in ("copy-start.5", "fusion.1")
    assert evs[-1].name == "all-reduce.2"
    assert evs[-1].end_us == pytest.approx(2400.0)


def test_gzip_and_plain_json_parse_identically(tmp_path):
    src = os.path.join(FIXTURE, "synthetic.trace.json")
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with open(src, "rb") as f, gzip.open(d / "host.trace.json.gz",
                                         "wb") as g:
        g.write(f.read())
    assert (events.load_device_events(str(tmp_path))
            == events.load_device_events(FIXTURE))


def test_cpu_fallback_selects_xla_executor_threads(tmp_path):
    # no /device:* process at all: the tf_XLA* pools under /host:CPU
    # stand in (the shape jax's CPU backend actually writes)
    doc = {"traceEvents": [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "tf_XLAEigen/12"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "dot.4",
         "ts": 10, "dur": 5, "args": {"hlo_op": "dot.4"}},
        {"ph": "X", "pid": 7, "tid": 1,
         "name": "ThreadpoolListener::StartRegion", "ts": 11, "dur": 1},
        {"ph": "X", "pid": 7, "tid": 2, "name": "host_thing",
         "ts": 10, "dur": 5},
    ]}
    (tmp_path / "x.trace.json").write_text(json.dumps(doc))
    evs = events.load_device_events(str(tmp_path))
    assert [e.name for e in evs] == ["dot.4"]   # infra + host excluded


def test_newest_capture_wins_by_mtime(tmp_path):
    import time
    now = time.time()
    for name, op, mtime in (("old", "stale.1", now - 500),
                            ("new", "fresh.2", now)):
        d = tmp_path / "plugins" / "profile" / name
        d.mkdir(parents=True)
        doc = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "X", "pid": 1, "tid": 1, "name": op, "ts": 1,
             "dur": 2}]}
        p = d / "t.trace.json.gz"
        with gzip.open(p, "wt") as f:
            json.dump(doc, f)
        os.utime(p, (mtime, mtime))
    assert [e.name for e in
            events.load_device_events(str(tmp_path))] == ["fresh.2"]


def test_xplane_and_json_paths_agree_on_real_capture(tmp_path):
    """Capture a real (tiny) CPU trace and parse BOTH formats: same
    op set, same durations — the stdlib fallback must not diverge
    from the proto path."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    with profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    js = events.load_device_events(str(tmp_path), prefer="json")
    xp = events.load_device_events(str(tmp_path), prefer="xplane")
    assert js, "capture produced no device events"
    assert {(e.name, round(e.dur_us, 1)) for e in js} \
        == {(e.name, round(e.dur_us, 1)) for e in xp}
    # hlo stats survive the proto's ref_value indirection
    assert any(e.hlo_module for e in xp)


# ---------------------------------------------------------------------------
# attribution buckets + overlap math


def test_classify_buckets():
    assert attribution.classify("fusion.123") == "compute"
    assert attribution.classify("dot.4") == "compute"
    assert attribution.classify("all-reduce.7") == "collective"
    assert attribution.classify("all-gather-start.2") == "collective"
    assert attribution.classify("reduce-scatter.1") == "collective"
    assert attribution.classify("collective-permute.9") == "collective"
    assert attribution.classify("infeed.1") == "transfer"
    assert attribution.classify("MemcpyD2H") == "transfer"
    assert attribution.classify("copy-start.3") == "transfer"
    # a device-local copy fusion is compute, not host traffic
    assert attribution.classify("copy.17") == "compute"


def test_fixture_breakdown_exact():
    bd = attribution.attribute(events.load_device_events(FIXTURE),
                               steps=2)
    assert bd.window_ms == pytest.approx(1.4)
    assert bd.compute_ms == pytest.approx(1.0)
    assert bd.collective_ms == pytest.approx(0.7)
    assert bd.transfer_ms == pytest.approx(0.06)
    assert bd.idle_ms == pytest.approx(0.05)
    assert bd.collective_hidden_ms == pytest.approx(0.35)
    assert bd.collective_exposed_ms == pytest.approx(0.35)
    assert bd.overlap_pct == pytest.approx(50.0)
    assert bd.step_ms == pytest.approx(0.7)
    assert bd.n_events == 8


def _ev(name, ts, dur):
    return events.DeviceEvent(name=name, start_us=ts, dur_us=dur)


def test_overlap_fully_hidden_vs_fully_trailing():
    # hidden: the collective runs entirely under concurrent compute
    hidden = attribution.attribute([
        _ev("fusion.1", 0, 100),
        _ev("all-reduce.1", 20, 50),
    ])
    assert hidden.overlap_pct == pytest.approx(100.0)
    assert hidden.collective_exposed_ms == pytest.approx(0.0)
    # trailing: the collective lands after backward finished — the
    # exact failure mode ROADMAP item 2 exists to fix
    trailing = attribution.attribute([
        _ev("fusion.1", 0, 100),
        _ev("all-reduce.1", 100, 50),
    ])
    assert trailing.overlap_pct == pytest.approx(0.0)
    assert trailing.collective_exposed_ms == pytest.approx(0.05)
    assert trailing.collective_hidden_ms == pytest.approx(0.0)


def test_overlap_async_pair_spans_inflight_gap():
    # start [0,10], compute [10,90], done [90,100]: the in-flight gap
    # counts as collective time and is fully hidden by the compute
    bd = attribution.attribute([
        _ev("all-reduce-start.1", 0, 10),
        _ev("fusion.1", 10, 80),
        _ev("all-reduce-done.1", 90, 10),
    ])
    assert bd.collective_ms == pytest.approx(0.1)
    assert bd.collective_hidden_ms == pytest.approx(0.08)
    assert bd.idle_ms == pytest.approx(0.0)


def test_no_collectives_reports_none_not_zero():
    bd = attribution.attribute([_ev("fusion.1", 0, 10)])
    assert bd.overlap_pct is None
    assert bd.collective_ms == 0.0


def test_empty_events():
    bd = attribution.attribute([])
    assert bd.window_ms == 0.0 and bd.n_events == 0
    assert bd.step_ms is None


def test_top_ops_table():
    rows = attribution.top_ops(events.load_device_events(FIXTURE),
                               top=3)
    assert [r["op"] for r in rows] == ["fusion.1", "fusion.2",
                                      "fusion.3"]
    assert rows[0]["category"] == "compute"
    assert rows[0]["total_ms"] == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# MFU chip table


def test_chip_table_lookup():
    assert profiler.chip_spec("TPU v5 lite").bf16_flops == 197e12
    assert profiler.chip_spec("TPU v5e").name == "TPU v5e"
    assert profiler.chip_spec("TPU v5p").bf16_flops == 459e12
    assert profiler.chip_spec("TPU v4").bf16_flops == 275e12
    assert profiler.chip_spec("TPU v6e").bf16_flops == 918e12
    assert profiler.chip_spec("Tesla A100") is None
    assert profiler.chip_spec("") is None


def test_mfu_arithmetic_and_refusals():
    # 1e12 flops in 10 ms on a 1e15-peak chip = 0.1
    assert profiler.mfu(1e12, 0.01, 1e15) == pytest.approx(0.1)
    assert profiler.mfu(None, 0.01, 1e15) is None
    assert profiler.mfu(1e12, None, 1e15) is None
    assert profiler.mfu(1e12, 0.01, None) is None
    assert profiler.mfu(1e12, 0.0, 1e15) is None


def test_step_flops_from_cost_analysis():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((64, 64), jnp.float32)
    flops = profiler.step_flops(f, a, a)
    # 2*M*N*K = 524288 when the backend reports; None is the
    # documented refusal, not a wrong number
    if flops is not None:
        assert flops == pytest.approx(2 * 64 ** 3, rel=0.5)


# ---------------------------------------------------------------------------
# report + CLI


def test_report_on_fixture_matches_readme():
    rep = profiler.build_report(FIXTURE)
    assert rep["steps"] == 2
    assert rep["step_ms"] == pytest.approx(0.7)
    assert rep["overlap_pct"] == pytest.approx(50.0)
    assert rep["mfu"] == pytest.approx(0.25)
    assert rep["mfu_source"] == "cost_analysis"
    bd = rep["breakdown"]
    assert (bd["compute_ms"], bd["collective_ms"], bd["transfer_ms"],
            bd["idle_ms"]) == (1.0, 0.7, 0.06, 0.05)


def test_profile_cli_json_and_text(capsys):
    from apex_tpu.telemetry import cli
    assert cli.main(["profile", FIXTURE, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["overlap_pct"] == 50.0
    assert rep["mfu"] == 0.25
    assert {"compute_ms", "collective_ms", "transfer_ms",
            "idle_ms"} <= set(rep["breakdown"])

    assert cli.main(["profile", FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "collective overlap: 50.0% hidden" in out
    assert "MFU: 0.2500" in out
    assert "fusion.1" in out


def test_profile_cli_empty_dir_exits_1(tmp_path, capsys):
    from apex_tpu.telemetry import cli
    assert cli.main(["profile", str(tmp_path)]) == 1
    assert "no device op events" in capsys.readouterr().out
    assert cli.main(["profile", str(tmp_path), "--json"]) == 1
    assert "error" in json.loads(capsys.readouterr().out)


def test_steps_override_beats_sidecar(tmp_path):
    shutil.copy(os.path.join(FIXTURE, "synthetic.trace.json"),
                tmp_path / "synthetic.trace.json")
    # no sidecar: no steps, no mfu — but the breakdown still renders
    rep = profiler.build_report(str(tmp_path))
    assert rep["steps"] is None and rep["mfu"] is None
    rep = profiler.build_report(str(tmp_path), steps=4)
    assert rep["step_ms"] == pytest.approx(0.35)


def test_perf_counters_land_in_session_jsonl(tmp_path):
    """emit_perf_counters -> hostmetrics -> session flush ->
    summarize's perf section, text and --json: the headline numbers
    ride the run's own telemetry."""
    import jax.numpy as jnp

    from apex_tpu import telemetry
    from apex_tpu.telemetry import cli

    run_dir = tmp_path / "run"
    tel = telemetry.Telemetry(str(run_dir), window=4, retrace=False)
    try:
        rep = profiler.build_report(FIXTURE)
        profiler.emit_perf_counters(rep)
        tel.record({"loss": jnp.float32(1.0)}, 0)
    finally:
        tel.close()

    buf = io.StringIO()
    assert cli.summarize(str(run_dir), as_json=True, out=buf) == 0
    doc = json.loads(buf.getvalue())
    assert doc["perf"]["overlap_pct"] == 50.0
    assert doc["perf"]["mfu"] == 0.25
    assert doc["perf"]["step_ms"] == pytest.approx(0.7)

    buf = io.StringIO()
    assert cli.summarize(str(run_dir), out=buf) == 0
    assert "perf (profiler capture)" in buf.getvalue()


def test_profile_window_end_to_end(tmp_path):
    """Real (CPU) capture through profile_window: sidecar written,
    report renders, flops recorded from cost analysis — and the
    perf/* headline counters published to an active session."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import telemetry

    f = jax.jit(lambda x: (jnp.tanh(x @ x.T),))
    x = jnp.ones((64, 64), jnp.float32)
    tel = telemetry.Telemetry(run_dir=None, window=4, retrace=False)
    try:
        meta = profiler.profile_window(f, x, steps=2,
                                       outdir=str(tmp_path / "tr"))
    finally:
        counters = {r["name"] for r in tel.counters.records()}
        tel.close()
    assert meta["steps"] == 2
    assert meta["flops_per_step"] and meta["mfu_source"] \
        == "cost_analysis"
    assert os.path.isfile(tmp_path / "tr" / "profile_meta.json")
    # the capture published its own headline counters (no manual
    # build_report + emit_perf_counters chain needed)
    assert {"perf/step_ms", "perf/compute_ms"} <= counters
    rep = profiler.build_report(str(tmp_path / "tr"))
    assert not rep.get("error")
    assert rep["steps"] == 2
    assert rep["breakdown"]["compute_ms"] > 0


def test_profile_window_threads_donated_state(tmp_path):
    import jax
    import jax.numpy as jnp

    donating = jax.jit(lambda s: (s + 1.0,), donate_argnums=(0,))
    meta = profiler.profile_window(
        donating, jnp.zeros((8,), jnp.float32), steps=3,
        outdir=str(tmp_path), thread_state=True)
    assert meta["steps"] == 3


def test_annotate_step_is_free():
    """The profiler-capable wrapper adds NOTHING to the program (the
    apexverify spec profiler.annotated_step holds the full flat-AMP
    step to this; here the minimal case pins jaxpr equality)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.sin(x) * 2.0

    x = jnp.ones((4,), jnp.float32)
    plain = jax.make_jaxpr(f)(x)
    wrapped = jax.make_jaxpr(profiler.annotate_step(f))(x)
    assert [str(e.primitive) for e in plain.eqns] \
        == [str(e.primitive) for e in wrapped.eqns]


def test_profiler_overhead_bench_smoke():
    from apex_tpu.telemetry.bench import bench_profiler_overhead
    out = bench_profiler_overhead(layers=2, hidden=16, iters=2, reps=1)
    assert out["profiler_on_ms"] > 0 and out["profiler_off_ms"] > 0
    assert "profiler_overhead_pct" in out


# ---------------------------------------------------------------------------
# pyprof mixed host+device summary (satellite)


def test_pyprof_merges_host_ranges_with_device_ops():
    from apex_tpu.pyprof import prof
    rows = prof.summarize_ops(FIXTURE)
    where = {r[1] for r in rows}
    assert where == {"device", "host"}
    host_rows = [r for r in rows if r[1] == "host"]
    # the named Pjit range is a host row; the $frame python-tracer row
    # is not
    assert [r[0] for r in host_rows] == ["PjitFunction(train_step)"]
    assert host_rows[0][3] == pytest.approx(100.0)   # share of host side
    dev = [r for r in rows if r[1] == "device"]
    assert dev[0][0] == "fusion.1"


def test_pyprof_main_renders_mixed_and_device_only(capsys):
    from apex_tpu.pyprof import prof
    assert prof.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "PjitFunction(train_step)" in out and "host" in out
    assert prof.main([FIXTURE, "--device-only"]) == 0
    out = capsys.readouterr().out
    assert "PjitFunction(train_step)" not in out
    assert prof.main([FIXTURE, "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert {"op", "where", "total_ms", "pct"} <= set(rows[0])


# ---------------------------------------------------------------------------
# perf_gate (pass / fail / noise band / trajectory)


def _write_round(root, n, backend, value, extra=None, parsed=True):
    doc = {"n": n}
    if parsed:
        doc["parsed"] = {"backend": backend, "value": value,
                         "extra": extra or {}}
    with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump(doc, f)


def _budget(metrics):
    return {"metrics": metrics}


def test_gate_passes_at_floor_and_within_noise(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 2000.0)
    _write_round(str(tmp_path), 2, "tpu", 1960.0)    # -2%: inside band
    verdicts = perf_gate.evaluate(
        _budget({"value": {"floor": 2000.0, "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert [v["status"] for v in verdicts] == ["ok"]


def test_gate_fails_above_noise_budget_breach(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 1800.0)    # -10% vs floor
    verdicts = perf_gate.evaluate(
        _budget({"value": {"floor": 2000.0, "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "regression"
    assert "floor" in verdicts[0]["detail"]


def test_gate_trajectory_regression_within_budget_slack(tmp_path):
    # floor is generous (1000) but the newest round slid >5% vs the
    # best prior hardware round — the trajectory check catches it
    _write_round(str(tmp_path), 1, "tpu", 2108.0)
    _write_round(str(tmp_path), 2, "tpu", 1900.0)
    verdicts = perf_gate.evaluate(
        _budget({"value": {"floor": 1000.0, "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "regression"
    assert "best prior" in verdicts[0]["detail"]


def test_gate_lower_is_better_ceiling(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 2000.0,
                 {"bert_step_ms": 140.0})
    verdicts = perf_gate.evaluate(
        _budget({"extra.bert_step_ms": {
            "ceiling": 133.0, "direction": "lower", "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "ok"          # within 5% of ceiling
    _write_round(str(tmp_path), 2, "tpu", 2000.0,
                 {"bert_step_ms": 160.0})
    verdicts = perf_gate.evaluate(
        _budget({"extra.bert_step_ms": {
            "ceiling": 133.0, "direction": "lower", "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "regression"


def test_gate_ignores_cpu_fallback_and_unparsed_rounds(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 2100.0)
    _write_round(str(tmp_path), 2, "cpu-fallback", 4.0)  # proxy line
    _write_round(str(tmp_path), 3, "tpu", 0.0)           # failed child
    _write_round(str(tmp_path), 4, "tpu", 2100.0, parsed=False)
    rounds = perf_gate.load_rounds(str(tmp_path))
    assert [n for n, _ in perf_gate.hardware_rounds(rounds)] == [1]
    verdicts = perf_gate.evaluate(
        _budget({"value": {"floor": 2000.0, "noise_pct": 5.0}}), rounds)
    assert verdicts[0]["status"] == "ok"
    assert verdicts[0]["rounds"] == [1]


def test_gate_stale_metric_fails_when_newest_round_drops_it(tmp_path):
    # r01 measured the metric, r02 (a valid hardware round) lost the
    # leg: grading r01's old value against the floor would mask the
    # failure — the verdict is stale and it gates
    _write_round(str(tmp_path), 1, "tpu", 2100.0, {"mfu": 0.3})
    _write_round(str(tmp_path), 2, "tpu", 2100.0)
    verdicts = perf_gate.evaluate(
        _budget({"extra.mfu": {"floor": 0.25, "noise_pct": 5.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "stale"
    budget = tmp_path / "budget.json"
    budget.write_text(json.dumps(
        _budget({"extra.mfu": {"floor": 0.25, "noise_pct": 5.0}})))
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--gate"]) == 1
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--report"]) == 0
    # auto mode cannot prove these stamp-less synthetic rounds postdate
    # the budget, so it reports without gating (the full auto-mode
    # date matrix lives in tests/test_autotune.py)
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path)]) == 0


def test_gate_non_numeric_value_skips_round_not_crashes(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 2100.0)
    _write_round(str(tmp_path), 2, "tpu", "n/a")   # hand-edited artifact
    rounds = perf_gate.load_rounds(str(tmp_path))
    assert [n for n, _ in perf_gate.hardware_rounds(rounds)] == [1]


def test_gate_no_data_metric(tmp_path):
    _write_round(str(tmp_path), 1, "tpu", 2100.0)
    verdicts = perf_gate.evaluate(
        _budget({"extra.never_measured": {"floor": 1.0}}),
        perf_gate.load_rounds(str(tmp_path)))
    assert verdicts[0]["status"] == "no-data"


def test_gate_empty_trajectory_grades_no_rounds(tmp_path, capsys):
    """An EMPTY BENCH trajectory is its own explicit verdict: one
    ``no-rounds`` line with the reason, exit 0 in auto/report mode —
    never the generic metric-by-metric cannot-compare chorus.  A
    forced --gate exits 1 (nothing on record can defend a budget)."""
    budget = tmp_path / "budget.json"
    budget.write_text(json.dumps(
        _budget({"value": {"floor": 2000.0, "noise_pct": 5.0}})))
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "no-rounds" in out and "empty" in out
    assert "no hardware round reports" not in out   # not the chorus
    assert out.count("\n") == 1                     # one line, done
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--report"]) == 0
    capsys.readouterr()
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--gate"]) == 1
    capsys.readouterr()
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["status"] == "no-rounds" and doc["verdicts"] == []


def test_gate_main_exit_codes_and_report_mode(tmp_path, capsys):
    budget = tmp_path / "budget.json"
    budget.write_text(json.dumps(
        _budget({"value": {"floor": 2000.0, "noise_pct": 5.0}})))
    _write_round(str(tmp_path), 1, "tpu", 1500.0)    # regression
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--gate"]) == 1
    capsys.readouterr()
    # --report: same verdicts, never gates
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--report"]) == 0
    assert "regression" in capsys.readouterr().out
    # --json stays parseable (and carries the chosen mode)
    assert perf_gate.main(["--budget", str(budget),
                           "--root", str(tmp_path), "--gate",
                           "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["regressions"] == 1
    assert doc["gating"] and "forced" in doc["mode_reason"]
    # missing budget: usage error, not a crash
    assert perf_gate.main(["--budget", str(tmp_path / "no.json"),
                           "--root", str(tmp_path)]) == 2


def test_gate_clean_on_committed_trajectory():
    """Zero exit on the repo's own (now empty: the old rounds were
    removed in PR 21) BENCH trajectory with the shipped budget."""
    assert perf_gate.main(["--json"]) == 0


# ---------------------------------------------------------------------------
# bench.py structured errors (satellite)


def test_bench_structured_errors():
    bench = _load_path("bench_mod", os.path.join(_ROOT, "bench.py"))
    e = bench._err("resnet50", "train_bench", "OOM at b256")
    assert e == {"leg": "resnet50", "stage": "train_bench",
                 "error": "OOM at b256"}


def test_bench_leg_failure_is_recorded_and_fails_the_run(capsys):
    """A failed leg is not swallowed: it lands structurally under
    ``errors`` on the flushed line (a non-empty ``errors`` is what
    makes run_child exit non-zero) and the next leg still runs."""
    bench = _load_path("bench_mod", os.path.join(_ROOT, "bench.py"))
    out = bench._empty_result()

    def boom():
        raise RuntimeError("OOM at b256")

    bench._leg(out, "resnet50", boom)
    bench._leg(out, "after", lambda: out["extra"].update(ran=True))
    assert out["errors"][0]["leg"] == "resnet50"
    assert "OOM at b256" in out["errors"][0]["error"]
    assert out["extra"]["ran"] is True
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2 and all(l["errors"] for l in lines)
    assert all(l["backend"] == "tpu" for l in lines)


def test_bench_refuses_a_non_tpu_backend(monkeypatch, capsys):
    """No CPU stand-in, no replayed record: pinned to another backend
    the bench body prints no result line and returns 2."""
    bench = _load_path("bench_mod", os.path.join(_ROOT, "bench.py"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.run_child() == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "TPU" in captured.err
    assert not hasattr(bench, "_cached_tpu_result")
