"""The hybrid linear-attention expert-decoder cell
(``kimi_linear_48b_a3b_adamw.hybrid_s4096``) on the CPU at its
``rehearsal`` sizes: the configuration against the catalog's row, its
yardstick by hand, a run end to end through ``run.py``, the control and
each of the model's faults coming out not ``correct``, and the reader
it brings.  Nothing here times anything, and no number of these runs is
a device metric.
"""

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

from benchmarks import check, counts_kda, run, traceread  # noqa: E402
from benchmarks.readers import kernel_max_roofline  # noqa: E402

CELL = "kimi_linear_48b_a3b_adamw.hybrid_s4096"
CONFIG = "kimi_linear_48b_a3b_adamw"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config``, copied: what the file has to carry
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}


def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def run_cell(*argv):
    """``run.main`` in this process; returns its last line, parsed."""
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(list(argv))
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---- the configuration as the manifest and the catalog want it -------------------

def test_configuration_keeps_every_published_number_and_cuts_three_counts():
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    config = run.load_json(ROOT, entry["file"])
    assert entry["reduced"] == config["reduced"] == list(CUT)
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    # the floors: a whole period after the dense layer, eight routed
    # experts, an eighth of the vocabulary
    lac = PUBLISHED["linear_attn_config"]
    assert config["num_hidden_layers"] == 5
    assert 4 in lac["full_attn_layers"] and {1, 2, 3, 5} <= set(
        lac["kda_layers"])
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # what the model reads beside the nested group, as published
    assert config["router_num_experts"] == PUBLISHED["num_experts"]
    assert (config["kda_num_heads"], config["kda_head_dim"],
            config["short_conv_kernel_size"], config["full_attn_layers"]) == (
                lac["num_heads"], lac["head_dim"],
                lac["short_conv_kernel_size"], lac["full_attn_layers"])
    assert {"KDA layer", "KDA initialisation", "MLA layer", "expert layer",
            "initializer", "optimizer", "sequence length"} <= set(
                config["assumed"])
    assert "32 chips" in config["deployment"]


def test_cell_is_one_chip_at_4k_and_reports_what_the_issue_lists():
    cell = run.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == 4096
    assert cell.traffic["trace_steps"] == 3
    reported = {m["name"] for s in ("end_to_end", "per_layer")
                for m in cell.metrics(s)}
    assert {"step_ms", "tokens_per_s", "peak_hbm_gib", "setup_s", "kda_ms",
            "kda_roofline", "attn_roofline", "xent_roofline",
            "moe_fill_ratio", "layernorm_ms", "linear_ms", "step_mfu",
            "fwd_bwd_ms", "optimizer_ms"} <= reported
    # the expert cell's test pins these three lists to that cell alone
    # (tests/benchmark/test_keye_cell.py), which this PR may not edit
    assert not {"indexer_ms", "opt_reduce_ms", "loop_exit_ms", "moe_ms",
                "expert_mm_roofline", "moe_max_load"} & reported
    new = [m for m in manifest()["per_layer"]
           if m["name"] in ("kda_ms", "kda_roofline")]
    assert len(new) == 2 and all(
        m["workloads"] == [CELL] and m["moves"] == "step_ms"
        and m["layer"] == "model kernels (ops/kda.py)" for m in new)


# ---- the yardstick ------------------------------------------------------------------

KINDS = [("kda", True), ("kda", False), ("kda", False), ("mla", False),
         ("kda", False)]


@pytest.mark.parametrize("got, want, rel", [
    # a chunk of 2 positions at dk = dv = 1: 2*4 + 4*2 + 4 + 6*2 = 32
    # forward, three times that with the backward
    (counts_kda.scan_flops(1, 1, 2, 1, 1, chunk=2), 3 * 32, 0),
    # a length that is not a chunk multiple counts the padded chunk
    (counts_kda.scan_flops(1, 1, 3, 1, 1, chunk=2), 2 * 3 * 32, 0),
    # the cell: 32 heads x 64 chunks x 8.91 MFLOP, 4 layers
    (counts_kda.scan_flops(1, 32, 4096, 128, 128, 4),
     3 * 4 * 2048 * (2 * 4096 * 128 + 4096 * 256 + 4096 * 128
                     + 6 * 64 * 128 * 128), 0),
    # a position reads q, k, v (2 B), g (4 B a channel), beta (4 B) and
    # writes o: (3*2 + 4) * 128 + 4 = 1284 in, 256 out; backward reads
    # the same, writes as much
    (counts_kda.scan_bytes(1, 1, 1, 128, 128), 3 * 1284 + 2 * 256, 0),
    # causal pairs of 4: 10; 6 * (dk + dv) a pair and head
    (counts_kda.mla_attention_flops(1, 2, 4, 3, 5), 6 * 2 * 8 * 10, 0),
    # the issue's parameter account: a KDA mixer 39.51 M (39.46 M in
    # matmuls), the MLA mixer 29.11 M
    (counts_kda.kda_mixer_params(2304, 32, 128), 39_460_864, 0),
    (counts_kda.mla_mixer_params(2304, 32, 512, 128, 64, 128), 29_114_368, 0),
    # 731 MFLOP a token forward: 9.0 TFLOP a step, forward and backward
    (counts_kda.step_flops(1, 4096, 2304, KINDS, 32, 32, 128, 512, 128, 64,
                           128, 9216, 1024, 8, 8, 256, 1, 20480),
     8.982e12, 1e-3),
])
def test_counts_agree_with_hand_worked_values(got, want, rel):
    assert got == pytest.approx(want, rel=rel)


# ---- a run, end to end, at the rehearsal sizes ------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_end_to_end(trace):
    last = run_cell("--workload", CELL, "--seed", str(2 ** 31 + 38),
                    "--seconds", "0.5", "--trace", trace, "--rehearse-cpu")
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["device"]["platform"] == "cpu"
    assert set(last["compared"]) == {"grad1_gap", "grad1_median_gap",
                                     "change3_gap", "change3_median_gap"}
    if trace == "0":
        assert {"step_ms", "tokens_per_s", "setup_s"} <= set(last["metrics"])
    else:
        # a CPU trace has no chip's plane: the device metrics are left
        # out, never reported as 0
        assert set(last["metrics"]) == {"host_loop_ms", "compiles_in_window"}


def test_an_unchanged_state_comes_out_not_correct(monkeypatch):
    from apex_tpu.optimizers._base import FusedOptimizerBase
    monkeypatch.setattr(FusedOptimizerBase, "step",
                        lambda self, grads, **kw: self.params)
    last = run_cell("--workload", CELL, "--seed", "3", "--seconds", "0.2",
                    "--rehearse-cpu")
    assert last["correct"] is False
    assert any(v["value"] is None or v["value"] > v["limit"]
               for v in last["compared"].values())


@pytest.fixture(scope="module")
def rehearsal_reference():
    """The seed's weights, batches and float32 reference at the
    rehearsal sizes."""
    cell = run.Cell(CELL, rehearse=True)
    job = cell.job(4, jax.devices())
    try:
        batches, spec = job.reference_batches(run.FIRST_STEPS), job.spec
        assert job.counts["expected_tokens_per_expert"] == 2 * 64 * 2 / 16
        assert max(int(t.max()) for t, _ in batches) < 512
    finally:
        job.close()
    return cell, spec, batches, cell.follow_reference(spec, 4, batches)


@pytest.mark.parametrize("precision, change", [
    ("fp8", {}),                                  # the control
    ("f32", {"kda_delta_rule": False}),           # beta fixed at 0
    ("f32", {"kda_channel_decay": False}),        # one decay a head
    ("f32", {"num_shared_experts": 0}),           # no shared expert
], ids=["control_fp8", "no_delta", "head_decay", "no_shared_expert"])
def test_the_control_and_each_fault_fail_a_limit(rehearsal_reference,
                                                 precision, change):
    """In the program's place, against the float32 reference: not
    correct; the reference against itself: correct."""
    cell, spec, batches, ref = rehearsal_reference
    kept = dict(cell.sizes)
    cell.sizes.update(change)
    try:
        low = cell.follow_reference(spec, 4, batches, precision)
    finally:
        cell.sizes = kept
    same, _ = check.decide(check.compare(ref, ref), cell.limits["limits"])
    ok, rows = check.decide(check.compare(low, ref), cell.limits["limits"])
    assert same and not ok, rows


# ---- the reader this cell brings ------------------------------------------------------

def _ctx(instructions):
    """Two steps of 100 ns of program ``step``; ``instructions`` the
    op line's events (by HLO instruction or kernel name)."""
    modules = [("jit_step(1)", 0.0, 100.0), ("jit_step(1)", 100.0, 200.0),
               ("jit_step(1)", 200.0, 300.0)]
    trace = traceread.Trace(
        devices={0: {traceread.MODULE_LINE: modules,
                     traceread.OP_LINE: list(instructions)}}, host=[])
    return types.SimpleNamespace(
        trace=trace, steady=traceread.steady_window(trace, "step"),
        peaks={"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
        counts={"kda_flops": 3.0, "kda_bytes": 5.0})


KERNELS = [("apex_kda_fwd", 10.0, 30.0), ("apex_kda_bwd", 120.0, 140.0),
           ("%fusion.7 = f32[8]{0} fusion(...)", 40.0, 60.0)]


def test_kda_roofline_takes_the_larger_bound_over_the_kernels_time():
    spec = run.load_json(ROOT, "benchmarks", "metrics", "kda_roofline.json")
    assert spec == {"reader": "kernel_max_roofline",
                    "params": {"prefix": "apex_kda_", "flops": "kda_flops",
                               "bytes": "kda_bytes"}}
    ctx = _ctx(KERNELS)
    # the kernels run 40 ns over two steps, 20 a step; 5 bytes at 1e9 B/s
    # (5 ns) bound it over 3 FLOPs at 1e9 FLOP/s (3 ns): 25 %
    assert kernel_max_roofline.read(ctx, **spec["params"]) \
        == pytest.approx(25.0)
    ctx.counts = {"kda_flops": 8.0, "kda_bytes": 5.0}
    assert kernel_max_roofline.read(ctx, **spec["params"]) \
        == pytest.approx(40.0)
    # a program without the kernels (the parent's): nothing, no error
    assert kernel_max_roofline.read(_ctx(KERNELS[2:]), **spec["params"]) \
        is None
    assert run.load_json(ROOT, "benchmarks", "metrics", "kda_ms.json") == {
        "reader": "scope_span", "params": {"scopes": ["apex_kda"]}}
