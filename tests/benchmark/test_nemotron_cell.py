"""The hybrid Mamba-2 / expert-decoder cell
(``nemotron_twotower_30b_a3b_adamw.ssm_s8192``) on the CPU at its
``rehearsal`` sizes: the configuration against the published numbers, its
yardstick by hand, a run end to end through ``run.py``, the control and
each of the model's faults coming out not ``correct``, and the metrics
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
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, counts_ssd, run, traceread, weights  # noqa: E402
from benchmarks.readers import kernel_max_roofline  # noqa: E402

CELL = "nemotron_twotower_30b_a3b_adamw.ssm_s8192"
CONFIG = "nemotron_twotower_30b_a3b_adamw"
# the published ``config.json``'s numbers: what the file has to carry
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 8, "vocab_size": 16384}


def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def run_cell(*argv):
    """``run.main`` in this process; returns its last line, parsed."""
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(list(argv))
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---- the configuration as the manifest wants it ----------------------------------

def test_configuration_keeps_every_published_number_and_cuts_three_counts():
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    config = run.load_json(ROOT, entry["file"])
    assert entry["reduced"] == config["reduced"] == list(CUT)
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    # the floors: a whole period of the pattern (it repeats every 7
    # layers up to layer 40), eight routed experts, an eighth of the
    # vocabulary
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert all(pattern[i] == pattern[i + 7] for i in range(33))
    assert sorted(pattern[:7]) == sorted("MMMEEE*")
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["vocab_size"] % 128 == 0          # the lane gate
    assert config["router_num_experts"] == PUBLISHED["n_routed_experts"]
    assert {"Mamba-2 mixer", "Mamba-2 initialisation", "attention layer",
            "expert layer", "initializer", "optimizer", "sequence length",
            "left out"} <= set(config["assumed"])
    assert "16 chips" in config["deployment"]


def test_cell_is_one_chip_at_8k_and_reports_its_new_metrics():
    cell = run.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["trace_steps"] == 3
    reported = {m["name"] for s in ("end_to_end", "per_layer")
                for m in cell.metrics(s)}
    assert {"step_ms", "tokens_per_s", "peak_hbm_gib", "setup_s", "ssd_ms",
            "ssd_roofline", "attn_roofline", "xent_roofline",
            "moe_fill_ratio", "layernorm_ms", "linear_ms", "step_mfu",
            "fwd_bwd_ms", "optimizer_ms"} <= reported
    # pinned to other cells by their own tests
    assert not {"indexer_ms", "opt_reduce_ms", "loop_exit_ms", "moe_ms",
                "expert_mm_roofline", "moe_max_load", "kda_ms",
                "kda_roofline", "setup_kernel_traces"} & reported
    new = [m for m in manifest()["per_layer"]
           if m["name"] in ("ssd_ms", "ssd_roofline")]
    assert len(new) == 2 and all(
        m["workloads"] == [CELL] and m["moves"] == "step_ms"
        and m["layer"] == "model kernels (ops/ssd.py)" for m in new)


# ---- the yardstick ------------------------------------------------------------------

@pytest.mark.parametrize("got, want, rel", [
    # a chunk of 2 positions, one head of 1 in one group of state 1:
    # C B^T 2*4, M x 2*4, C S^T and x^T B 2*2 each: 24 forward, three
    # times that with the backward
    (counts_ssd.scan_flops(1, 1, 1, 2, 1, 1, chunk=2), 3 * 24, 0),
    # a length that is not a chunk multiple counts the padded chunk
    (counts_ssd.scan_flops(1, 1, 1, 3, 1, 1, chunk=2), 2 * 3 * 24, 0),
    # the cell: 3 layers x 64 chunks, C B^T for 8 groups, 64 heads
    (counts_ssd.scan_flops(1, 64, 8, 8192, 64, 128, 3),
     3 * 3 * 64 * (8 * 2 * 128 ** 2 * 128
                   + 64 * (2 * 128 ** 2 * 64 + 4 * 128 * 128 * 64)), 0),
    # a position reads x (64 x 2 B), B and C (128 x 2 B each), dt and L
    # (4 B each) and writes y (128 B); backward reads the same and dy,
    # writes as much as it read of the inputs
    (counts_ssd.scan_bytes(1, 1, 1, 1, 64, 128), 3 * 648 + 2 * 128, 0),
    # the parameter account: a mixer's matmuls 38.71 M (38.74 M
    # with its convolution, dt_bias, A_log, D and norm), attention 23.40 M
    (counts_ssd.mamba_mixer_params(2688, 64, 64, 8, 128), 38_707_200, 0),
    (counts_ssd.attention_params(2688, 32, 2, 128), 23_396_352, 0),
    # 3072 expected assignments a layer, up and down of 2688 x 1856
    (counts_ssd.expert_flops(8192, 2688, 1856, 6, 8, 128),
     6 * 3072 * 2 * 2688 * 1856, 0),
    # 244.46 M dense matmul weights a token: 14.47 TFLOP a step
    (counts_ssd.step_flops(1, 8192, 2688, list("MEMEM*E"), heads=64,
                           head_dim=64, groups=8, state=128, attn_heads=32,
                           kv_heads=2, attn_head_dim=128, width=1856,
                           shared_width=3712, top_k=6, held=8,
                           router_width=128, vocab=16384),
     14.468e12, 1e-3),
])
def test_counts_agree_with_hand_worked_values(got, want, rel):
    assert got == pytest.approx(want, rel=rel)


# ---- a run, end to end, at the rehearsal sizes ------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_end_to_end(trace):
    last = run_cell("--workload", CELL, "--seed", str(2 ** 31 + 41),
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
    ("fp8", {}),                                    # the control
    ("f32", {"ssd_decay": False}),                  # exp(dt A) = 1
    ("f32", {"ssd_d_skip": False}),                 # no D x
    ("f32", {"gate_norm_groups": 1}),               # one norm over 4096
    ("f32", {"expert_act": "relu"}),                # relu for relu^2
    ("f32", {"ssd_group_of_head": "tiled"}),        # B, C tiled over heads
], ids=["control_fp8", "no_decay", "no_d_skip", "norm_whole", "relu",
        "bc_tiled"])
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


def test_the_selection_bias_settles_each_experts_load_at_its_share():
    """``balance`` at the rehearsal sizes: on the batches it settles on,
    every expert of each expert layer takes its share of the top-k
    choices (48 of 3 x 2 x 64 tokens x 2 over 16 experts) to within a
    token or two; without ``balanced_start_batches`` no bias moves."""
    cell = run.Cell(CELL, rehearse=True)
    ref, sizes = cell.reference, cell.sizes
    assert sizes["balanced_start_batches"] == run.FIRST_STEPS == 3
    params = jax.jit(lambda p: ref.initial(p, sizes))(
        weights.make(ref.param_spec(sizes), 7))
    tokens = list(jax.random.randint(jax.random.key(7), (3, 2, 64), 0,
                                     sizes["vocab_size"]))
    biases = ref.balance(params, tokens, sizes)
    assert sorted(biases) == ["layer_1", "layer_3", "layer_6"]
    params = ref.with_biases(params, biases)
    progs = ref._Programs(sizes, "f32")
    hs = [params["embed"]["weight"][t] for t in tokens]
    k, experts = sizes["num_experts_per_tok"], sizes["router_num_experts"]
    share = 3 * 2 * 64 * k / experts
    for i, kind in enumerate(progs.kinds):
        p = params[f"layer_{i}"]
        if kind == "E":
            x = jnp.concatenate([ref._rms_norm(
                h, p["norm"]["weight"], sizes["layer_norm_epsilon"]
            ).reshape(-1, sizes["hidden_size"]) for h in hs])
            _, chosen = ref.route(x, p["moe"]["router"],
                                  p["moe"]["selection_bias"], k, 1.0,
                                  lambda v: v)
            load = jnp.sum(chosen[..., None] == jnp.arange(experts),
                           axis=(0, 1))
            assert int(jnp.max(jnp.abs(load - share))) <= 2, (i, load)
        hs = [progs.layer[kind](p, h) for h in hs]
    assert ref.balance(params, tokens,
                       dict(sizes, balanced_start_batches=0)) == {}
    with pytest.raises(ValueError):
        ref.balance(params, tokens[:2], sizes)


def test_the_faults_named_are_the_references():
    from benchmarks.reference import nemotron_twotower_30b_a3b_adamw as ref
    assert set(ref.FAULTS) == {"fault_no_decay", "fault_no_d_skip",
                               "fault_norm_whole", "fault_relu",
                               "fault_bc_tiled"}


# ---- the metrics this cell brings ------------------------------------------------------

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
        counts={"ssd_flops": 8.0, "ssd_bytes": 2.0})


KERNELS = [("apex_ssd_fwd", 10.0, 30.0), ("apex_ssd_bwd", 120.0, 140.0),
           ("apex_kda_fwd", 150.0, 190.0),
           ("%fusion.7 = f32[8]{0} fusion(...)", 40.0, 60.0)]


def test_ssd_roofline_takes_the_larger_bound_over_the_kernels_time():
    spec = run.load_json(ROOT, "benchmarks", "metrics", "ssd_roofline.json")
    assert spec == {"reader": "kernel_max_roofline",
                    "params": {"prefix": "apex_ssd_", "flops": "ssd_flops",
                               "bytes": "ssd_bytes"}}
    ctx = _ctx(KERNELS)
    # the two kernels run 40 ns over two steps, 20 a step; 8 FLOPs at
    # 1e9 FLOP/s (8 ns) bound it over 2 bytes: 40 %
    assert kernel_max_roofline.read(ctx, **spec["params"]) \
        == pytest.approx(40.0)
    # a program without the kernels (the parent's): nothing, no error
    assert kernel_max_roofline.read(_ctx(KERNELS[2:]), **spec["params"]) \
        is None
    assert run.load_json(ROOT, "benchmarks", "metrics", "ssd_ms.json") == {
        "reader": "scope_span", "params": {"scopes": ["apex_ssd"]}}
