"""The benchmark's own tests (BENCHMARK.json, benchmarks/): quick, on the
CPU.  Nothing here times anything: sizes are each configuration's
``rehearsal`` preset, Pallas kernels run interpreted, and no number of
these runs is a device metric.  No TPU topology is described anywhere.
"""

import importlib
import io
import json
import math
import os
import re
import sys
from contextlib import redirect_stdout

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, counts, run, traceread, weights  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BERT, RESNET = "bert_large_lamb.phase2_s512", "resnet50_sgd.one_chip"


def manifest(with_parked=False):
    """BENCHMARK.json; with the cells of benchmarks/parked.json merged
    in (they keep to the same rules, to be moved across as they are)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    if with_parked:
        with open(os.path.join(ROOT, "benchmarks", "parked.json")) as f:
            parked = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            m[key] = m[key] + parked[key]
    return m


def run_cell(*argv):
    """``run.main`` in this process; returns its last line, parsed."""
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(list(argv))
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---- the manifest and the files found by name ------------------------------

@pytest.mark.parametrize("with_parked", [False, True])
def test_manifest_parses_and_every_name_and_unit_is_legal(with_parked):
    m = manifest(with_parked)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in m["paths"])
    names = [c["name"] for c in m["configs"]] \
        + [w["name"] for w in m["workloads"]] \
        + [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    for w in m["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert all(NAME.match(n) for n in names), names
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(x["unit"]) and x["moves"] in e2e
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in m["end_to_end"] + m["per_layer"]:
        assert set(x.get("workloads", cells)) <= cells
    for c in m["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert any(w["config"] == c["name"] for w in m["workloads"])


def test_every_cell_finds_its_files_by_name():
    for w in manifest(with_parked=True)["workloads"]:
        cell = run.Cell(w["name"])
        assert cell.traffic["chips"] == cell.chips and cell.limits["limits"]
        driver = importlib.import_module(
            "benchmarks.drivers." + cell.sizes["driver"])
        assert hasattr(driver, "Job")
        assert callable(cell.reference.param_spec)
        assert callable(cell.reference.follow)
        for section in ("end_to_end", "per_layer"):
            entries = cell.metrics(section)
            assert entries
            for x in entries:
                spec = run.load_json(ROOT, "benchmarks", "metrics",
                                     x["name"] + ".json")
                reader = importlib.import_module(
                    "benchmarks.readers." + spec["reader"])
                assert callable(reader.read)


# ---- the yardstick ------------------------------------------------------------

@pytest.mark.parametrize("got, want, rel", [
    # BERT-Large b8 s512: 24 layers x 2*4096*12*1024^2 = 2.474e12, head
    # 2*4096*1024*30528 = 0.256e12, x3, + attention 12*8*16*512^2*64*24
    (counts.bert_step_flops(8, 512, 1024, 24, 16, 4096, 30528),
     3 * (2.4739e12 + 0.25609e12) + 0.6185e12, 1e-3),
    # ResNet-50 at 224: 4.09 GMACs forward, x2 FLOPs, x3 for the step
    (counts.resnet_step_flops(1), 3 * 2 * 4.089e9, 2e-3),
    (counts.resnet_forward_flops(), 8.178e9, 1e-3),
    # attention proper: 6 matmuls of 2*b*h*s*s*d
    (counts.attention_flops(8, 16, 512, 64), 6 * 2 * 8 * 16 * 512 * 512 * 64,
     1e-12),
    # LAMB with masters: g 2 + (m, v) 16 + master 8 + model 2 = 28 B/param
    (counts.optimizer_bytes("lamb", 1000), 28e3, 1e-12),
    # SGD momentum with masters: 2 + 8 + 8 + 2 = 20 B/param
    (counts.optimizer_bytes("sgd_momentum", 1000), 20e3, 1e-12),
])
def test_counts_agree_with_hand_worked_values(got, want, rel):
    assert got == pytest.approx(want, rel=rel)


def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    assert counts.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        counts.peaks("cpu")


def test_interval_union_agrees_with_hand_built_intervals():
    merged = traceread.merge([(5, 7), (0, 2), (1, 3), (7, 7), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert traceread.total(merged) == 7
    assert traceread.intersect(merged, [(2, 6), (8, 20)]) == [
        (2, 3), (5, 6), (8, 9)]
    assert traceread.gaps(merged, (1, 12)) == [(3, 5), (9, 12)]


def test_worst_leaf_gap_and_the_negligible_gradient_rule():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-9}
    # c is measured against the median leaf (1.0), not its own norm
    gap, leaf = check.worst_leaf_gap({"a": 10.5, "b": 1.0, "c": 0.0}, ref)
    assert (leaf, gap) == ("a", pytest.approx(0.05))
    assert check.negligible_leaves(ref) == {"c"}
    numbers = check.compare(
        {"losses": [1.0, 2.0], "grad1": ref, "change": {**ref, "c": 5.0}},
        {"losses": [1.0, 2.2], "grad1": ref, "change": ref})
    assert numbers["change2_gap"]["value"] == 0.0        # c is left out
    ok, rows = check.decide(numbers, {"loss2_gap": 0.05, "grad1_gap": 0.1})
    assert not ok and [r["ok"] for r in rows] == [False, True]
    assert not check.decide({}, {"loss1_gap": 1.0})[0]   # number missing


def test_weights_repeat_for_a_seed_and_take_seeds_past_32_bits():
    spec = {"a": {"w": ((4, 3), ("normal", 0.5)), "b": ((3,), ("zeros",))}}
    one, again = weights.make(spec, 2 ** 31 + 17), weights.make(
        spec, 2 ** 31 + 17)
    other = weights.make(spec, 2 ** 33 + 2 ** 31 + 17)
    assert (one["a"]["w"] == again["a"]["w"]).all()
    assert not (one["a"]["w"] == other["a"]["w"]).all()
    assert (one["a"]["b"] == 0).all()


# ---- the trace reader -----------------------------------------------------------

def test_trace_reader_reduces_the_recorded_trace():
    """``recorded_trace.json``: three steps of resnet50_sgd.one_chip on a
    TPU v5e, cut from what ``benchmarks/record_trace.py`` kept (PR 24;
    event names shortened to 72 characters)."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        trace = traceread.Trace.from_json(json.load(f))
    window = traceread.steady_window(trace, "train_step")
    assert window is not None and window.steps >= 2
    busy = traceread.busy_seconds(trace, window)
    assert 0 < busy <= window.seconds
    fwd = traceread.clipped(traceread.module_events(trace, 0, "train_step"),
                            window)
    opt = traceread.clipped(
        traceread.module_events(trace, 0, "_full_step_flat"), window)
    assert len(fwd) == window.steps and len(opt) == window.steps
    fwd_s = sum(e - s for _, s, e in fwd) / 1e9
    assert 0.5 * busy < fwd_s <= window.seconds
    ops = traceread.top_ops(trace, window)
    assert 1 <= len(ops) <= 10 and ops == sorted(ops, key=lambda r: -r[1])
    gaps = traceread.idle_gaps(trace, window)
    assert sum(s for _, s in gaps) == pytest.approx(
        window.seconds - busy, rel=1e-6)
    assert traceread.kernel_seconds(trace, window, "apex_multi_tensor_sgd") > 0
    assert traceread.kernel_seconds(trace, window, "apex_flash_attention") == 0


def test_xplane_file_is_read_with_jax_alone(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("input_wait"):
            jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    path = traceread.find_xplane(str(tmp_path))
    assert any(r["plane"] == traceread.HOST_PLANE
               for r in traceread.describe_xplane(path))
    trace = traceread.load_xplane(path, ["input_wait"])
    assert [e[0] for e in trace.host] == ["input_wait"]
    assert trace.devices == {}           # a CPU trace has no chip's plane
    assert traceread.steady_window(trace, "step") is None


# ---- a run, end to end, at the rehearsal sizes ------------------------------------

def test_run_refuses_to_measure_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        run_cell("--workload", BERT, "--seed", "1", "--seconds", "1")
    assert "no accelerator" in str(e.value)


@pytest.mark.parametrize("cell, trace, rate", [
    (BERT, "0", "tokens_per_s"), (RESNET, "1", None)])
def test_rehearsal_runs_end_to_end_and_names_its_device(cell, trace, rate):
    seed = str(2 ** 31 + 5)
    last = run_cell("--workload", cell, "--seed", seed, "--seconds", "0.5",
                    "--trace", trace, "--rehearse-cpu")
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    if rate:
        assert {"step_ms", rate, "setup_s"} <= set(last["metrics"])
        assert "peak_hbm_gib" not in last["metrics"]     # none on a CPU
    else:
        # a CPU trace has no chip's plane: the device metrics are left
        # out, never reported as 0
        assert set(last["metrics"]) == {"host_loop_ms", "compiles_in_window"}
        assert last["metrics"]["compiles_in_window"]["value"] == 0
    m = manifest(with_parked=True)
    units = {x["name"]: x["unit"] for x in m["end_to_end"] + m["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in last["metrics"].items())


def _unchanged_state(monkeypatch, cell):
    from apex_tpu.optimizers._base import FusedOptimizerBase
    monkeypatch.setattr(FusedOptimizerBase, "step",
                        lambda self, grads, **kw: self.params)


def _half_batch(monkeypatch, cell):
    job = importlib.import_module(
        "benchmarks.drivers." + run.Cell(cell, True).sizes["driver"]).Job
    whole = job.forward_backward
    monkeypatch.setattr(
        job, "forward_backward", lambda self, batch: whole(
            self, tuple(a[:len(a) // 2] for a in batch)))


@pytest.mark.parametrize("cell", [BERT, RESNET])
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch, cell)
    last = run_cell("--workload", cell, "--seed", "3", "--seconds", "0.2",
                    "--rehearse-cpu")
    assert last["correct"] is False
    assert any(v["value"] is None or v["value"] > v["limit"]
               for v in last["compared"].values())


@pytest.mark.parametrize("cell", [BERT, RESNET])
def test_the_control_in_lower_precision_fails_a_limit(cell):
    """The reference in the control's precision (fp8 operands, one step
    below amp O2's bfloat16) in the program's place: not correct."""
    cell = run.Cell(cell, rehearse=True)
    job = cell.job(4, jax.devices())
    try:
        batches, spec = job.reference_batches(run.FIRST_STEPS), job.spec
    finally:
        job.close()
    ref, low = (cell.follow_reference(spec, 4, batches, p)
                for p in ("f32", "fp8"))
    same, _ = check.decide(check.compare(ref, ref), cell.limits["limits"])
    ok, rows = check.decide(check.compare(low, ref), cell.limits["limits"])
    assert same and not ok, rows


def test_resnet_driver_with_ddp_and_sync_bn_on_four_virtual_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs --xla_force_host_platform_device_count>=4")
    cell = run.Cell(RESNET, rehearse=True)
    traffic = run.load_json(ROOT, "benchmarks", "traffic",
                            "ddp4_syncbn.json")
    traffic.update(traffic["rehearsal"])
    cell.chips = traffic["chips"]
    job = cell.job(6, jax.devices(), traffic=traffic)
    try:
        program = run.first_steps(job)
        x, _ = job._last_args[-2:]
        assert len(x.sharding.device_set) == 4
        batches, spec = job.reference_batches(run.FIRST_STEPS), job.spec
    finally:
        job.close()
    ref = cell.follow_reference(spec, 6, batches)
    assert set(program["grad1"]) == set(ref["grad1"])
    ok, rows = check.decide(check.compare(program, ref),
                            cell.limits["limits"])
    assert ok, rows
    assert all(math.isfinite(x) for x in program["losses"])
