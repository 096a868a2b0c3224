"""The sparse-attention expert-decoder cell
(``keye_vl2_30b_a3b_adamw.longctx_s8192``) on the CPU at its
``rehearsal`` sizes: the configuration against the catalog's row, its
yardstick by hand, a run end to end through ``run.py``, the control and
each fault coming out not ``correct``, and its two readers.  Nothing
here times anything, and no number of these runs is a device metric.
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

from benchmarks import check, counts_moe, programtrace, run  # noqa: E402
from benchmarks import traceread  # noqa: E402
from benchmarks.readers import job_count, scope_and_kernel_time  # noqa: E402

CELL = "keye_vl2_30b_a3b_adamw.longctx_s8192"
CONFIG = "keye_vl2_30b_a3b_adamw"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config``, copied: what the file has to carry
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 5, "num_experts": 8, "num_local_experts": 8,
       "vocab_size": 18992}


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
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    # no cut is of a width, and the floors hold: a whole period and four
    # layers, eight routed experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width, the indexer its sizes
    assert config["router_num_experts"] == PUBLISHED["num_experts"]
    sa = PUBLISHED["sa_config"]
    assert (config["indexer_num_heads"], config["indexer_head_dim"],
            config["indexer_topk"]) == (
                sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert {"vision tower", "per-head q/k norm", "indexer",
            "indexer objective", "expert layer", "initializer",
            "optimizer"} <= set(config["assumed"])
    assert "16 chips" in config["deployment"]


def test_cell_is_one_chip_at_8k_and_reports_what_the_issue_lists():
    cell = run.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["trace_steps"] == 3
    reported = {m["name"] for s in ("end_to_end", "per_layer")
                for m in cell.metrics(s)}
    assert {"step_ms", "tokens_per_s", "peak_hbm_gib", "setup_s", "moe_ms",
            "indexer_ms", "expert_mm_roofline", "moe_max_load",
            "attn_roofline", "layernorm_ms", "linear_ms", "step_mfu",
            "opt_roofline", "fwd_bwd_ms", "optimizer_ms"} <= reported
    # 18992 columns are no lane multiple: no Pallas cross-entropy here
    assert not {"xent_roofline", "opt_reduce_ms", "loop_exit_ms"} & reported
    new = [m for m in manifest()["per_layer"] if m["name"] in (
        "moe_ms", "indexer_ms", "expert_mm_roofline", "moe_max_load")]
    assert all(m["workloads"] == [CELL] and m["moves"] == "step_ms"
               for m in new) and len(new) == 4


# ---- the yardstick ------------------------------------------------------------------

@pytest.mark.parametrize("got, want, rel", [
    # a query keeps min(t + 1, topk) keys: 1 + 2 + 3 + 3 + 3
    (counts_moe.selected_pairs(5, 3), 12, 0),
    (counts_moe.selected_pairs(3, 8), 6, 0),
    # the cell: 2048 * 2049 / 2 + 6144 * 2048 = 14.68 M of 33.56 M
    (counts_moe.selected_pairs(8192, 2048), 14_681_088, 0),
    (counts_moe.causal_pairs(8192), 33_558_528, 0),
    # 12 * heads * d a selected pair: 2 matmuls forward, 4 backward
    (counts_moe.attention_flops(1, 32, 8192, 128, 2048),
     12 * 32 * 128 * 14_681_088, 1e-12),
    (counts_moe.attention_flops(1, 32, 8192, 128, 2048, 5), 3.608e12, 1e-3),
    # dense causal attention when topk covers the sequence
    (counts_moe.attention_flops(2, 4, 16, 8, 64),
     12 * 2 * 4 * 8 * 136, 1e-12),
    # the indexer scores every causal pair: 6 * heads * d a pair
    (counts_moe.indexer_flops(1, 16, 8192, 64, 5),
     6 * 16 * 64 * 33_558_528 * 5, 1e-12),
    # 8192 * 8 * 8 / 128 = 4096 assignments, 3 * 2048 * 768 = 4.72 M
    # parameters an expert, 6 FLOPs each: 115.96 G a layer
    (counts_moe.expert_flops(8192, 2048, 768, 8, 8, 128),
     6 * 4096 * 4_718_592, 1e-12),
    (counts_moe.expert_flops(8192, 2048, 768, 8, 8, 128, 5), 5.798e11, 1e-3),
    # a layer outside its experts: q/k/v 2048 x 5120, o 4096 x 2048,
    # indexer 2048 x 1104, router 2048 x 128 = 21.397 M; the head's slice
    # 38.90 M: 6 * 8192 * (5 * 21.397 M + 38.90 M) = 7.170e12, + experts
    # 0.580e12 + attention 3.608e12 + index scores 1.031e12
    (counts_moe.step_flops(1, 8192, 2048, 5, 32, 4, 128, 768, 8, 8, 128, 16,
                           64, 2048, 18992), 12.389e12, 1e-3),
    # one layer, no vocabulary, by hand: per token 6 * (4*16 + 8*4 + 4*5
    # + 4*8) = 888 over 4 tokens; experts 6 * (4*2*4/8) * 3*4*2 = 576;
    # attention 12 * 1*2*4 * 10 pairs = 960; index scores 6 * 1*2 * 10
    (counts_moe.step_flops(1, 4, 4, 1, 2, 1, 4, 2, 2, 4, 8, 1, 2, 8, 0),
     4 * 888 + 576 + 960 + 120, 1e-12),
    (counts_moe.xent_bytes(8192, 18992), 3 * 8192 * 18992 * 4, 1e-12),
])
def test_counts_agree_with_hand_worked_values(got, want, rel):
    assert got == pytest.approx(want, rel=rel)


# ---- a run, end to end, at the rehearsal sizes ------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_end_to_end(trace):
    last = run_cell("--workload", CELL, "--seed", str(2 ** 31 + 31),
                    "--seconds", "0.5", "--trace", trace, "--rehearse-cpu")
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["device"]["platform"] == "cpu"
    assert set(last["compared"]) == {"grad1_gap", "grad1_median_gap",
                                     "change3_gap", "change3_median_gap"}
    if trace == "0":
        assert {"step_ms", "tokens_per_s", "setup_s"} <= set(last["metrics"])
    else:
        # a CPU trace has no chip's plane: the device metrics are left
        # out, never reported as 0; the job's own count is there
        assert set(last["metrics"]) == {"host_loop_ms", "compiles_in_window",
                                        "moe_max_load"}
        assert last["metrics"]["moe_max_load"]["value"] >= 1.0


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
    built = driver.SparseMoEDecoder
    monkeypatch.setattr(driver, "SparseMoEDecoder",
                        lambda **kw: built(**{**kw, **change(kw)}))


def _selection_left_out(monkeypatch, driver):
    _model_with(monkeypatch, driver, lambda kw: {"index_topk": 1 << 20})


def _one_expert_less(monkeypatch, driver):
    _model_with(monkeypatch, driver, lambda kw: {"top_k": kw["top_k"] - 1})


def _no_indexer_objective(monkeypatch, driver):
    _model_with(monkeypatch, driver, lambda kw: {"index_loss_weight": 0.0})


@pytest.mark.parametrize("fault", [
    _unchanged_state, _half_of_every_sequence, _selection_left_out,
    _one_expert_less, _no_indexer_objective])
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
        assert job.counts["expected_tokens_per_expert"] == 2 * 64 * 2 / 16
        assert max(int(t.max()) for t, _ in batches) < 2048
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
    name, start, end) inside them, ``instructions`` the same kind of
    events as the op line names them (by HLO instruction)."""
    modules = [("jit_step(1)", 0.0, 100.0), ("jit_step(1)", 100.0, 200.0),
               ("jit_step(1)", 200.0, 300.0)]
    trace = traceread.Trace(
        devices={0: {traceread.MODULE_LINE: modules,
                     traceread.OP_LINE: list(instructions)}}, host=[])
    return types.SimpleNamespace(
        trace=trace, steady=traceread.steady_window(trace, "step"),
        program_trace=programtrace.ProgramTrace(
            [], {0: [(n, s, e, "step") for n, s, e in ops]}))


OPS = [
    ("jit(step)/transpose(jvp(checkpoint))/layer_0/moe/apex_moe/experts/"
     "apex_swiglu/mul:", 110.0, 120.0),
    ("jit(step)/jvp(checkpoint)/layer_0/moe/apex_moe/dispatch/gather:",
     120.0, 160.0),
    ("jit(step)/jvp(checkpoint)/layer_0/apex_sparse_attn/select/"
     "apex_index_select/pallas_call:", 160.0, 190.0),
]
# the grouped products as the compiler names them: no scope reaches them
KERNELS = [
    ("%ragged-dot-none.3 = bf16[64,32]{1,0} custom-call(...)", 10.0, 30.0),
    ("%ragged-dot-metadata.1 = (s32[9]{0}) custom-call(...)", 30.0, 32.0),
    ("%fusion.7 = f32[8]{0} fusion(...)", 40.0, 60.0)]


def test_scope_and_kernel_time_adds_the_compilers_kernels_to_the_scope():
    ctx = _ctx(OPS, KERNELS)
    # (10 + 40) ns under apex_moe and (20 + 2) ns of ragged-dot kernels,
    # over two steps
    assert scope_and_kernel_time.read(ctx, ["apex_moe"], ["ragged-dot"]) \
        == pytest.approx(72.0 / 1e6 / 2)
    assert scope_and_kernel_time.read(ctx, ["apex_moe"], []) \
        == pytest.approx(50.0 / 1e6 / 2)
    assert scope_and_kernel_time.read(ctx, ["apex_nothing"], ["ragged-dot"]) \
        == pytest.approx(22.0 / 1e6 / 2)
    # a program with neither (the parent's): nothing, and no error
    bare = _ctx([("jit(step)/apex_linear/dot_general:", 10.0, 20.0)],
                KERNELS[2:])
    assert scope_and_kernel_time.read(bare, ["apex_moe"],
                                      ["ragged-dot"]) is None


def test_expert_mm_roofline_reads_the_grouped_products_by_name():
    spec = run.load_json(ROOT, "benchmarks", "metrics",
                         "expert_mm_roofline.json")
    assert spec == {"reader": "kernel_roofline",
                    "params": {"prefix": "ragged-dot",
                               "count": "expert_flops"}}
    ctx = _ctx(OPS, KERNELS)
    ctx.peaks, ctx.counts = {"flops_per_s": 1e9}, {"expert_flops": 3.3}
    from benchmarks.readers import kernel_roofline
    # 3.3 FLOPs at 1e9 FLOP/s are 3.3 ns a step; the kernels ran 22 ns
    # over the two steps, 11 a step
    assert kernel_roofline.read(ctx, **spec["params"]) == pytest.approx(30.0)


def test_job_count_reads_what_the_job_fetched_or_nothing():
    ctx = types.SimpleNamespace(counts={"moe_max_load": 1.25})
    assert job_count.read(ctx, "moe_max_load") == 1.25
    assert job_count.read(ctx, "no_such_count") is None
