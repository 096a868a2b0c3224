"""Reference-shaped test driver (reference: tests/L0/run_test.py, which
selects suites like run_amp / run_optimizers / run_fused_layer_norm /
run_transformer — SURVEY.md §4).

This repo's suites are plain pytest; this driver maps the reference's
suite names onto them so the reference's invocation habit
(`python tests/run_test.py --include run_amp`) keeps working.

    python tests/run_test.py                      # fast tier (default)
    python tests/run_test.py --tier full          # everything (nightly)
    python tests/run_test.py --include run_amp run_optimizers

Tiers (VERDICT r2 #9): the default FAST tier excludes tests marked
``slow`` (integration-weight suites, listed centrally in
tests/conftest.py) and round-trips in ~5 minutes on the 1-core CI box;
the FULL tier runs everything and is the nightly/pre-merge bar.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

SUITES = {
    "run_amp": ["tests/test_amp.py", "tests/test_amp_wrap.py",
                "tests/test_amp_flat_pipeline.py",
                "tests/test_grad_accum.py",
                "tests/test_fp8.py",
                "tests/test_L1_trajectory.py",
                "tests/test_torch_amp.py"],
    "run_optimizers": ["tests/test_multi_tensor.py",
                       "tests/test_optimizers.py",
                       "tests/test_bucketed_optimizers.py",
                       "tests/test_flat_step_one_sweep.py",
                       "tests/test_optimizer_ownership.py",
                       "tests/test_distributed_optimizers.py"],
    "run_fused_layer_norm": ["tests/test_fused_layer_norm.py"],
    "run_fused_softmax": ["tests/test_fused_softmax_rope.py"],
    "run_mlp": ["tests/test_fused_dense.py"],
    "run_transformer": ["tests/test_tensor_parallel.py",
                        "tests/test_pipeline_parallel.py",
                        "tests/test_comm.py", "tests/test_moe.py",
                        "tests/test_sparse_moe_decoder.py",
                        "tests/test_microbatches.py"],
    "run_fp16util": ["tests/test_fp16_rnn_reparam.py"],
    "run_attention": ["tests/test_attention.py",
                      "tests/test_sparse_index.py",
                      "tests/test_contrib_multihead_attn.py"],
    "run_contrib": ["tests/test_contrib_xentropy_clipgrad.py",
                    "tests/test_contrib_transducer.py",
                    "tests/test_contrib_misc.py",
                    "tests/test_sparsity_pyprof.py"],
    "run_distributed": ["tests/test_parallel.py",
                        "tests/test_wgrad.py",
                        "tests/test_distributed_launch.py"],
    "run_checkpoint": ["tests/test_native_checkpoint.py",
                       "tests/test_resilience.py",
                       "tests/test_fleet.py",
                       "tests/test_fleet_grow.py",
                       # incident-id correlation + the merged fleet
                       # timeline (telemetry timeline CLI)
                       "tests/test_incident_timeline.py"],
    "run_models": ["tests/test_models.py",
                   "tests/test_looped_decoder.py"],
    "run_examples": ["tests/test_examples_smoke.py"],
    "run_data": ["tests/test_data.py"],
    "run_offload": ["tests/test_offload.py"],
    "run_quantization": ["tests/test_quantization.py"],
    # harness/tooling logic (platform select, amortized timer, the
    # kernel-bench distillers that write dispatch defaults, and the
    # autotuner + per-topology dispatch tables + perf_gate auto mode)
    "run_harness": ["tests/test_platform.py", "tests/test_benchlib.py",
                    "tests/test_kernel_bench_logic.py",
                    "tests/test_autotune.py"],
    "run_lint": ["tests/test_lint.py"],
    # apexverify: jaxpr-level invariant specs over the public jitted
    # entry points + the findings-baseline diff gate (tools/check.sh)
    "run_lint_semantic": ["tests/test_lint_semantic.py"],
    # apexrace: thread-root/shared-state/lock-domain analysis over the
    # whole package + the races it surfaced (regression tests)
    "run_lint_concurrency": ["tests/test_lint_concurrency.py"],
    # apexcost: donation-aware liveness cost cards + the committed
    # ledger diff gate + the ddp telemetry cross-check
    "run_lint_cost": ["tests/test_lint_cost.py"],
    # the serving path: paged KV arena, AOT prefill/decode programs,
    # the continuous-batching engine and its chaos matrix (hung
    # decode, shed, drain, replica failover)
    "run_serving": ["tests/test_serving.py",
                    # request-level lifecycle traces + SLO histograms
                    # (gap-free under chaos, cross-host failover lanes)
                    "tests/test_reqtrace.py"],
    # run-time training telemetry (metric ring, emitters, spans,
    # retrace counter) + the pyprof nvtx/prof satellites + the live
    # /metrics exporter
    "run_telemetry": ["tests/test_telemetry.py",
                      "tests/test_export.py",
                      # spans as profiler events; the hot path's own
                      # apex/* spans and apex_* scopes
                      "tests/test_telemetry_spans.py",
                      "tests/test_hot_path_scopes.py"],
    # the performance observatory: trace parsing, attribution/overlap,
    # cost-model MFU, report CLI, and the perf regression gate
    "run_profiler": ["tests/test_profiler.py"],
    # AOT Mosaic lowering for the TPU platform — runs in CPU CI
    "run_tpu_lowering": ["tests/test_tpu_lowering.py"],
    "run_tpu_compile": ["tests/test_tpu_compile.py"],
    # TPU-only: needs APEX_TPU_SMOKE=1 and a real chip (else skips)
    "run_tpu_smoke": ["tests/test_tpu_smoke.py"],
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--include", nargs="+", default=None,
                   help=f"suites: {sorted(SUITES)}")
    p.add_argument("--exclude", nargs="*", default=[])
    p.add_argument("--tier", choices=("fast", "full"), default="fast",
                   help="fast (default): skip @slow tests; "
                        "full: run everything (nightly bar)")
    args, passthrough = p.parse_known_args()

    names = args.include if args.include else sorted(SUITES)
    unknown = [n for n in names + args.exclude if n not in SUITES]
    if unknown:
        p.error(f"unknown suites {unknown}; available: {sorted(SUITES)}")
    files: list = []
    for n in names:
        if n not in args.exclude:
            files += SUITES[n]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tier = ["-m", "not slow"] if args.tier == "fast" else []
    cmd = [sys.executable, "-m", "pytest", "-q", *tier, *files,
           *passthrough]
    print(" ".join(cmd))
    sys.exit(subprocess.call(cmd, cwd=root))


if __name__ == "__main__":
    main()
