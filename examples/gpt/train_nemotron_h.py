"""Hybrid Mamba-2 / expert-decoder pretraining step: amp O2 + FusedAdam
(AdamW) with global-norm clipping, over
``apex_tpu.models.nemotron_h.NemotronHDecoder`` — Mamba-2 mixers (a
chunked SSD scan), non-gated squared-ReLU experts under a sigmoid
router with a shared expert, and grouped-query attention without
positions, in the order a pattern string gives, this chip holding
``--experts-held`` of the router's experts.

The step and the optimizer are the expert example's (``train_moe.py``:
``build_step``, ``build_optimizer``, ``MAX_BUCKET_BYTES``); the model
comes from a configuration's keys (``build_model``, the names of the
model's ``config.json`` as the benchmark's configuration file keeps
them).  Synthetic next-token batches (no corpus on disk).

Usage:
    python examples/gpt/train_nemotron_h.py [--steps 20] [--layers 7]
        [--experts-held 8] [--batch-size 1] [--seq-len 8192]

Sizes are Nemotron-Labs-TwoTower-30B-A3B's widths, layers 0-6 (one
period of its pattern, ``MEMEM*E``), 8 of 128 experts and an eighth of
the vocabulary, on whatever backend jax starts; ``--cpu`` is the
explicit small proxy the tests run.  ``main(argv)`` returns a summary
dict (losses, found_inf total, step time).
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))  # repo-root run, no install

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp
from apex_tpu.models.nemotron_h import NemotronHDecoder


def _expert_example():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "train_moe.py")
    spec = importlib.util.spec_from_file_location("_train_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_moe = _expert_example()
build_step = _moe.build_step
build_optimizer = _moe.build_optimizer
MAX_BUCKET_BYTES = _moe.MAX_BUCKET_BYTES
WARMUP_STEPS = 2

# Nemotron-Labs-TwoTower-30B-A3B (config.json), a chip's share of layers
# 0-6 under 16-way expert parallelism and an eighth of the vocabulary
PUBLISHED = {
    "hidden_size": 2688, "num_hidden_layers": 7,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
    "ssm_state_size": 128, "conv_kernel": 4, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "router_num_experts": 128, "n_routed_experts": 8, "expert_offset": 0,
    "num_experts_per_tok": 6, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "vocab_size": 16384}
CPU_PROXY = dict(
    PUBLISHED, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, router_num_experts=16,
    n_routed_experts=4, num_experts_per_tok=2, vocab_size=512)


def build_model(sizes: dict, dtype=jnp.bfloat16) -> NemotronHDecoder:
    """The decoder a configuration's keys describe: ``n_routed_experts``
    is the experts held here, ``router_num_experts`` the router's
    width."""
    pattern = sizes["hybrid_override_pattern"]
    return NemotronHDecoder(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"], pattern=pattern,
        mamba=dict(num_heads=sizes["mamba_num_heads"],
                   head_dim=sizes["mamba_head_dim"],
                   n_groups=sizes["n_groups"],
                   state_size=sizes["ssm_state_size"],
                   conv_size=sizes["conv_kernel"],
                   time_step_min=sizes["time_step_min"],
                   time_step_max=sizes["time_step_max"],
                   time_step_floor=sizes["time_step_floor"]),
        attention=dict(num_heads=sizes["num_attention_heads"],
                       num_kv_heads=sizes["num_key_value_heads"],
                       head_dim=sizes["head_dim"]),
        experts=dict(ffn_hidden_size=sizes["moe_intermediate_size"],
                     num_experts=sizes["router_num_experts"],
                     experts_held=sizes["n_routed_experts"],
                     top_k=sizes["num_experts_per_tok"],
                     expert_offset=sizes["expert_offset"],
                     norm_topk_prob=bool(sizes["norm_topk_prob"]),
                     routed_scale=float(sizes["routed_scaling_factor"]),
                     shared_ffn_hidden_size=sizes[
                         "moe_shared_expert_intermediate_size"]),
        rms_norm_eps=sizes["layer_norm_epsilon"], dtype=dtype)


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--layers", type=int, default=7)
    p.add_argument("--experts-held", type=int, default=0,
                   help="experts computed here; default 8 (4 with --cpu)")
    p.add_argument("--batch-size", type=int, default=0,
                   help="default 1 (2 with --cpu)")
    p.add_argument("--seq-len", type=int, default=0,
                   help="default 8192 (64 with --cpu)")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU backend, "
                        "hidden 64, 4 Mamba heads of 16, 4 of 16 experts, "
                        "top-2, b2 s64")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes (hidden 64, 4 Mamba "
              "heads of 16, 4 of 16 experts, b2 s64 unless given)")
    sizes = dict(CPU_PROXY if args.cpu else PUBLISHED,
                 num_hidden_layers=args.layers)
    if args.experts_held:
        sizes["n_routed_experts"] = args.experts_held
    batch = args.batch_size or (2 if args.cpu else 1)
    seq = args.seq_len or (64 if args.cpu else 8192)
    model = build_model(sizes)
    print(f"apex_tpu {apex_tpu.__version__}: hybrid Mamba-2 expert decoder "
          f"{model.pattern[:model.num_layers]} h{model.hidden_size} "
          f"{model.experts['experts_held']}/{model.experts['num_experts']} "
          f"experts top-{model.experts['top_k']} + shared, amp O2 b{batch} "
          f"s{seq} on {jax.default_backend()}")

    # ONE fixed synthetic batch: overfitting it makes the descent visible
    tokens = jax.random.randint(jax.random.key(1), (batch, seq + 1), 0,
                                model.vocab_size)
    tokens, labels = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.key(0), tokens, labels)["params"]
    params, amp_state = amp.initialize(params, opt_level="O2")
    opt, amp_state = build_optimizer(
        params, amp_state, lr=args.lr, betas=(0.9, 0.95),
        weight_decay=args.weight_decay)
    del params
    step = build_step(model, amp_state)

    losses, infs = [], []
    t0 = None
    for i in range(args.steps):
        loss, grads, found_inf, clip_coef, aux = step(
            opt.params, amp_state.scaler, tokens, labels)
        opt.step(grads, found_inf=found_inf, clip_coef=clip_coef)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(loss)
        infs.append(found_inf)
        if i == WARMUP_STEPS - 1:
            jax.block_until_ready((loss, opt.params))
            t0 = time.perf_counter()
        if i % 5 == 0:
            # 1-in-5-steps console echo, not a per-step sync
            print(f"step {i:3d} loss {float(loss):.4f}")   # apexlint: disable=APX102
    jax.block_until_ready(opt.params)
    timed = args.steps - WARMUP_STEPS
    summary = {"losses": [float(x) for x in losses],
               "found_inf": sum(int(x) for x in infs),
               "timed_steps": max(timed, 0), "step_ms": None,
               "expert_counts": jax.device_get(
                   aux["expert_counts"]).tolist()}
    if timed > 0:
        dt = (time.perf_counter() - t0) / timed
        summary["step_ms"] = dt * 1e3
        print(f"step time {dt*1e3:.1f} ms  "
              f"({batch * seq / dt:.0f} tokens/sec)")
    return summary


if __name__ == "__main__":
    main()
