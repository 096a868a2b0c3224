"""The looped-decoder cell (``ouro_2p6b_adamw.pretrain_s4096``) on the
CPU at its ``rehearsal`` sizes: end to end through ``run.py``, the
control and each fault coming out not ``correct``, its yardstick by
hand, and its two readers.  Nothing here times anything, and no number
of these runs is a device metric.
"""

import importlib
import io
import json
import os
import sys
import types
from contextlib import redirect_stdout

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, counts_looped, programtrace, run  # noqa: E402
from benchmarks import traceread  # noqa: E402
from benchmarks.readers import kernel_bytes_roofline, scope_span  # noqa: E402

CELL = "ouro_2p6b_adamw.pretrain_s4096"


def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def run_cell(*argv):
    """``run.main`` in this process; returns its last line, parsed."""
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(list(argv))
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---- the configuration as the manifest and the catalog want it -------------------

def test_configuration_keeps_the_published_widths_and_cuts_depth_alone():
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "ouro_2p6b_adamw")
    config = run.load_json(ROOT, entry["file"])
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    published = {"hidden_size": 2048, "num_attention_heads": 16,
                 "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "vocab_size": 49152,
                 "total_ut_steps": 4, "rms_norm_eps": 1e-6,
                 "rope_theta": 1000000, "max_position_embeddings": 65536,
                 "max_window_layers": 48, "early_exit_threshold": 1}
    assert {k: config[k] for k in published} == published
    assert config["tie_word_embeddings"] is False
    assert config["num_hidden_layers"] == 4
    assert {"sandwich norms", "norm between passes", "exit gate",
            "exit_entropy_weight", "biases", "initializer"} <= set(
                config["assumed"])
    cell = run.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == 4096
    reported = {m["name"] for s in ("end_to_end", "per_layer")
                for m in cell.metrics(s)}
    assert {"step_ms", "tokens_per_s", "peak_hbm_gib", "setup_s",
            "loop_exit_ms", "xent_roofline", "attn_roofline",
            "layernorm_ms", "linear_ms", "step_mfu",
            "opt_roofline"} <= reported
    assert "opt_reduce_ms" not in reported


# ---- the yardstick ------------------------------------------------------------------

@pytest.mark.parametrize("got, want, rel", [
    # causal attention proper: 6 matmul passes of b*heads*s*s*d (half of
    # the 12 a full square needs); 16 applications at the cell's size
    (counts_looped.causal_attention_flops(1, 16, 4096, 128),
     6 * 16 * 4096 * 4096 * 128, 1e-12),
    (counts_looped.causal_attention_flops(1, 16, 4096, 128, 16),
     3.2985e12, 1e-4),
    # a layer is 4*2048^2 + 3*2048*5632 = 51.38 M, the head 100.66 M:
    # 6 * 4096 * (16 * 51.38 M + 4 * 100.66 M) = 30.10e12, + attention
    (counts_looped.looped_step_flops(1, 4096, 2048, 4, 16, 128, 5632, 49152,
                                     4), 30.100e12 + 3.2985e12, 1e-3),
    # one pass of one layer and no vocabulary: 6 * tokens * parameters
    (counts_looped.looped_step_flops(2, 8, 4, 1, 1, 4, 8, 0, 1),
     6 * 16 * (4 * 16 + 3 * 32) + 6 * 2 * 64 * 4, 1e-12),
    # per pass: logits read forward, read and written backward, float32
    (counts_looped.xent_bytes(4096, 49152, 4), 3 * 4 * 4096 * 49152 * 4,
     1e-12),
    (counts_looped.xent_bytes(10, 100, 1, logit_bytes=2), 6000.0, 1e-12),
])
def test_looped_counts_agree_with_hand_worked_values(got, want, rel):
    assert got == pytest.approx(want, rel=rel)


# ---- a run, end to end, at the rehearsal sizes ------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_end_to_end(trace):
    last = run_cell("--workload", CELL, "--seed", str(2 ** 31 + 27),
                    "--seconds", "0.5", "--trace", trace, "--rehearse-cpu")
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["device"]["platform"] == "cpu"
    assert set(last["compared"]) == {"grad1_gap", "grad1_median_gap",
                                     "change3_gap", "change3_median_gap"}
    if trace == "0":
        assert {"step_ms", "tokens_per_s", "setup_s"} <= set(last["metrics"])
    else:
        # a CPU trace has no chip's plane: the device metrics, this
        # cell's three among them, are left out, never reported as 0
        assert set(last["metrics"]) == {"host_loop_ms", "compiles_in_window"}


def _unchanged_state(monkeypatch, driver):
    from apex_tpu.optimizers._base import FusedOptimizerBase
    monkeypatch.setattr(FusedOptimizerBase, "step",
                        lambda self, grads, **kw: self.params)


def _half_of_every_sequence(monkeypatch, driver):
    whole = driver.Job.forward_backward
    monkeypatch.setattr(
        driver.Job, "forward_backward", lambda self, batch: whole(
            self, tuple(a[:, :a.shape[1] // 2] for a in batch)))


def _model_with(monkeypatch, driver, change):
    built = driver.LoopedDecoder
    monkeypatch.setattr(driver, "LoopedDecoder",
                        lambda **kw: built(**{**kw, **change(kw)}))


def _three_passes_for_four(monkeypatch, driver):
    _model_with(monkeypatch, driver,
                lambda kw: {"num_passes": kw["num_passes"] - 1})


def _no_entropy_term(monkeypatch, driver):
    _model_with(monkeypatch, driver, lambda kw: {"entropy_weight": 0.0})


@pytest.mark.parametrize("fault", [
    _unchanged_state, _half_of_every_sequence, _three_passes_for_four,
    _no_entropy_term])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    driver = importlib.import_module(
        "benchmarks.drivers." + run.Cell(CELL, True).sizes["driver"])
    fault(monkeypatch, driver)
    last = run_cell("--workload", CELL, "--seed", "3", "--seconds", "0.2",
                    "--rehearse-cpu")
    assert last["correct"] is False
    assert any(v["value"] is None or v["value"] > v["limit"]
               for v in last["compared"].values())


def test_the_control_in_lower_precision_fails_a_limit():
    """The reference in fp8 (one step below amp O2's bfloat16) in the
    program's place: not correct; against itself: correct."""
    cell = run.Cell(CELL, rehearse=True)
    job = cell.job(4, jax.devices())
    try:
        batches, spec = job.reference_batches(run.FIRST_STEPS), job.spec
        assert job.counts["loop_passes"] == 4
        assert job.counts["layer_applications"] == 8
    finally:
        job.close()
    ref, low = (cell.follow_reference(spec, 4, batches, p)
                for p in ("f32", "fp8"))
    same, _ = check.decide(check.compare(ref, ref), cell.limits["limits"])
    ok, rows = check.decide(check.compare(low, ref), cell.limits["limits"])
    assert same and not ok, rows


# ---- the two readers this cell brings ------------------------------------------------------

def _ctx(ops, instructions=()):
    """Two steps of 100 ns of program ``step``; ``ops`` are (framework
    name, start, end) inside them, ``instructions`` the same events as
    the op line names them (by HLO instruction)."""
    modules = [("jit_step(1)", 0.0, 100.0), ("jit_step(1)", 100.0, 200.0),
               ("jit_step(1)", 200.0, 300.0)]
    trace = traceread.Trace(
        devices={0: {traceread.MODULE_LINE: modules,
                     traceread.OP_LINE: list(instructions)}},
        host=[])
    return types.SimpleNamespace(
        trace=trace, steady=traceread.steady_window(trace, "step"),
        peaks={"hbm_bytes_per_s": 1e9}, counts={"xent_bytes": 30.0},
        program_trace=programtrace.ProgramTrace(
            [], {0: [(n, s, e, "step") for n, s, e in ops]}))


OPS = [
    ("jit(step)/jvp(apex_loop/exit)/final_norm/apex_layernorm/"
     "apex_fused_rms_norm_fwd/pallas_call:", 10.0, 20.0),
    ("jit(step)/transpose(jvp(apex_loop/exit))/apex_linear/dot_general:",
     20.0, 50.0),
    ("jit(step)/jvp(apex_loop/exit)/apex_xentropy/apex_xentropy_fwd/"
     "pallas_call:", 110.0, 120.0),
    ("jit(step)/jvp(apex_loop/gate)/dot_general:", 120.0, 124.0),
    ("jit(step)/jvp(apex_loop/body)/layer_0/apex_linear/dot_general:",
     130.0, 190.0),
    ("jit(step)/apex_loop_other/exit/add:", 190.0, 195.0),
]


def test_scope_span_reads_a_scope_with_all_it_encloses():
    ctx = _ctx(OPS)
    assert ctx.steady.steps == 2
    # (10 + 30 + 10 + 4) ns over two steps; scope_time would give the
    # exit's matmul to apex_linear alone
    assert scope_span.read(ctx, ["apex_loop/exit", "apex_loop/gate"]) \
        == pytest.approx(54.0 / 1e6 / 2)
    assert scope_span.read(ctx, ["apex_loop/body"]) == pytest.approx(
        60.0 / 1e6 / 2)
    assert scope_span.read(ctx, ["apex_swiglu"]) is None     # fused away
    # the parent's program has no such scope: nothing, and no error
    assert scope_span.read(_ctx([("jit(step)/apex_linear/dot_general:",
                                  10.0, 20.0)]), ["apex_loop/exit"]) is None


def test_kernel_bytes_roofline_divides_bytes_by_the_kernels_time():
    ctx = _ctx(OPS, [
        ("%apex_xentropy_fwd.3 = f32[8,128]{1,0} custom-call(...)",
         110.0, 120.0),
        ("%apex_xentropy_bwd.1 = f32[8,256]{1,0} custom-call(...)",
         130.0, 160.0),
        ("%fusion.7 = f32[8]{0} fusion(...)", 160.0, 190.0)])
    # 30 bytes at 1e9 B/s are 30 ns a step; the kernels ran 40 ns over
    # the two steps, 20 a step
    assert kernel_bytes_roofline.read(
        ctx, "apex_xentropy", "xent_bytes") == pytest.approx(150.0)
    assert kernel_bytes_roofline.read(
        ctx, "apex_nothing", "xent_bytes") is None
    assert kernel_bytes_roofline.read(
        ctx, "apex_xentropy", "no_such_count") is None
