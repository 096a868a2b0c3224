"""Test harness: simulate an 8-chip topology on CPU host devices.

The reference cannot simulate multi-GPU (SURVEY.md §4: distributed tests
skip without >=2 real GPUs).  JAX can: force 8 host-platform devices and
run every DP/TP/PP/SP suite on a real Mesh in one process.  Pallas kernels
run in interpreter mode off-TPU (apex_tpu.ops._dispatch).

The suite is CPU-only whatever the environment says (``JAX_PLATFORMS=cpu``
is how the driver runs it; a bare ``pytest tests/`` gets the same by
the config pin below, which must come BEFORE the first backend use).
The one exception is the on-chip kernel suite, tests/test_tpu_smoke.py,
selected with ``APEX_TPU_SMOKE=1``.
"""

import os

import jax

# import-time env reads are THE POINT here: the backend must be chosen
# before the first jax.devices() call (module docstring), so they
# cannot move into a function called later.
if os.environ.get("APEX_TPU_SMOKE") == "1":   # apexlint: disable=APX601
    # TPU smoke mode (tests/test_tpu_smoke.py): keep the real backend and
    # persist compiled executables so re-runs skip the slow first compile.
    from apex_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
else:
    jax.config.update("jax_platforms", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")   # apexlint: disable=APX601
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

# ---- speed tiers (VERDICT r2 #9) -----------------------------------
# The box CI runs on has ONE core (no xdist win), so the fast tier is
# a marker filter: `-m "not slow"` (the tests/run_test.py default)
# finishes in ~5 min; the nightly full tier runs everything.  Slow
# tests are listed HERE, centrally, so the list can be regenerated
# from `pytest --durations=60` without touching every file; the
# threshold for membership is ≥ ~5s of single-test wall time.
SLOW_MODULES = {
    "test_L1_trajectory.py",      # reference L1 tier: whole-training
    "test_examples_smoke.py",     # reference L6 tier: runs examples
    "test_distributed_launch.py",  # spawns multi-process jax workers
}
SLOW_TESTS = {
    "test_grad_accum.py::test_overlap_schedule_bench_smoke",
    "test_models.py::test_gpt_single_device_loss_decreases",
    "test_models.py::test_resnet18_forward_and_train_step",
    "test_models.py::test_gpt_tp_matches_tp1",
    "test_models.py::test_gpt_packed_tp_matches_tp1",
    "test_models.py::test_gpt_packed_batch_matches_per_sequence",
    "test_models.py::test_bert_packed_batch_matches_per_sequence",
    "test_models.py::test_gpt_tp_GRADS_match_tp1",
    "test_models.py::test_bert_tp_GRADS_match_tp1",
    "test_models.py::test_4d_assembly_grads_match_single_device",
    "test_models.py::test_bert_tp_matches_tp1",
    "test_models.py::test_gpt_layer_context_parallel_matches_full",
    "test_models.py::test_bert_forward_shapes_and_mask",
    "test_contrib_transducer.py::"
    "test_loss_grad_is_finite_and_correct_vs_numerical",
    "test_offload.py::test_gpt_layer_tags_compose_with_offload",
    "test_parallel.py::test_ddp_syncbn_resnet_config5_matches_full_batch",
    "test_contrib_misc.py::test_spatial_bottleneck_matches_unsharded",
    "test_contrib_misc.py::test_spatial_bottleneck_grads_with_group_psum",
    "test_contrib_misc.py::test_bottleneck_shapes_and_residual",
    "test_attention.py::test_ring_attention_grads_match_full",
    "test_attention.py::test_ring_kernel_matches_ring_ref",
    "test_attention.py::test_flash_attention_multiblock_tiling",
    "test_attention.py::test_single_kv_fast_path_matches_generic_kernel",
    "test_attention.py::test_flash_attention_segment_ids_grads",
    "test_attention.py::test_ulysses_attention_grads_match_full",
    "test_moe.py::test_expert_parallel_grads_finite_and_match",
    "test_moe.py::test_single_rank_matches_oracle",
    "test_amp_wrap.py::test_scan_over_layers_gpt_block_bf16_inside",
    "test_tensor_parallel.py::test_tp_mlp_forward_and_grads_match_dense",
    "test_tensor_parallel.py::test_sequence_parallel_mlp_matches_dense",
    "test_fused_softmax_rope.py::test_causal_softmax_matches_ref_and_grads",
    "test_contrib_multihead_attn.py::"
    "test_fmha_packed_matches_per_sequence_attention",
    "test_kernel_bench_logic.py::test_tiny_cpu",  # packed-varlen bench
    # three CLI subprocesses, each paying the jax import; the tier-1
    # lint gate is test_package_self_check, which stays fast-tier
    "test_lint.py::test_cli_exit_codes_and_json",
    # runs the full toy example (60 amp steps) in-process
    "test_telemetry.py::test_train_toy_telemetry_end_to_end",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: integration-weight test excluded from the fast tier "
        "(tests/run_test.py default); the full tier runs everything")


def pytest_collection_modifyitems(config, items):
    """Smoke mode pins the real TPU backend for the whole process, so
    only the smoke file may run — deselect everything else rather than
    letting CPU-intended mesh suites loose on the chip.
    Otherwise: centrally apply the `slow` marker."""
    if os.environ.get("APEX_TPU_SMOKE") == "1":
        keep = [it for it in items if "test_tpu_smoke" in str(it.fspath)]
        drop = [it for it in items
                if "test_tpu_smoke" not in str(it.fspath)]
        if drop:
            config.hook.pytest_deselected(items=drop)
            items[:] = keep
        return
    for it in items:
        fname = os.path.basename(str(it.fspath))
        base = getattr(it, "originalname", None) or it.name
        if fname in SLOW_MODULES or f"{fname}::{base}" in SLOW_TESTS:
            it.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_mesh():
    """Each test sees a fresh (uninitialized) global mesh."""
    from apex_tpu import comm
    comm.destroy()
    yield
    comm.destroy()


@pytest.fixture(autouse=True)
def _neutral_dispatch(monkeypatch):
    """Pin kernel dispatch to its design default (prefer Pallas) for
    every test: a measured dispatch_prefs*.json table or an exported
    APEX_TPU_PREFER_* in the developer's shell must never silently
    reroute kernel-correctness tests onto the reference path (they
    would then assert ref-vs-ref and a real kernel bug would pass CI).
    Dispatch-mechanism tests override _PREFS/env explicitly."""
    from apex_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "_PREFS", {})
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {})
    monkeypatch.setattr(_dispatch, "_PIPELINE", {})
    monkeypatch.setattr(_dispatch, "_FP8", {})
    monkeypatch.setattr(_dispatch, "_QUANT", {})
    monkeypatch.setattr(_dispatch, "_SERVING", {})
    monkeypatch.setattr(_dispatch, "_INSTALLED", None)
    monkeypatch.delenv("APEX_TPU_PREFER_PALLAS", raising=False)
    monkeypatch.delenv("APEX_TPU_PREFER_XLA", raising=False)


@pytest.fixture
def mesh8():
    from apex_tpu import comm
    return comm.initialize(data=2, pipe=1, ctx=1, model=4)
