"""Hybrid linear-attention expert-decoder pretraining step: amp O2 +
FusedAdam (AdamW) with global-norm clipping, over
``apex_tpu.models.kimi_linear.KimiLinearDecoder`` — Kimi Delta
Attention layers (a chunked gated delta rule) and latent attention
without positions, 3 : 1, a dense first layer, then sigmoid-routed
experts with a shared expert, this chip holding ``--experts-held`` of
the router's ``--experts``.

The step and the optimizer are the expert example's (``train_moe.py``:
``build_step``, ``build_optimizer``, ``MAX_BUCKET_BYTES``); the model
comes from a configuration's keys (``build_model``, the names of the
model's ``config.json`` as the benchmark's configuration file keeps
them).  Synthetic next-token batches (no corpus on disk).

Usage:
    python examples/gpt/train_kimi_linear.py [--steps 20] [--layers 5]
        [--experts-held 8] [--batch-size 1] [--seq-len 4096]

Sizes are Kimi-Linear-48B-A3B's widths, layers 1-5 (one period of
3 KDA : 1 MLA after the dense layer), 8 of 256 experts and an eighth of
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
from apex_tpu.models.kimi_linear import KimiLinearDecoder


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

# Kimi-Linear-48B-A3B (config.json), a chip's share of layers 1-5 under
# 32-way expert parallelism and an eighth of the vocabulary
PUBLISHED = {
    "hidden_size": 2304, "num_hidden_layers": 5, "num_attention_heads": 32,
    "kda_num_heads": 32, "kda_head_dim": 128, "short_conv_kernel_size": 4,
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "first_k_dense_replace": 1, "intermediate_size": 9216,
    "moe_intermediate_size": 1024, "router_num_experts": 256,
    "num_experts": 8, "num_experts_per_token": 8, "expert_offset": 0,
    "routed_scaling_factor": 2.446, "num_shared_experts": 1,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "rms_norm_eps": 1e-5, "vocab_size": 20480}
CPU_PROXY = dict(
    PUBLISHED, hidden_size=64, num_attention_heads=2, kda_num_heads=2,
    kda_head_dim=32, intermediate_size=96, moe_intermediate_size=32,
    router_num_experts=16, num_experts=4, num_experts_per_token=2,
    kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, vocab_size=512)


def build_model(sizes: dict, dtype=jnp.bfloat16) -> KimiLinearDecoder:
    """The decoder a configuration's keys describe: ``num_experts`` is
    the experts held here, ``router_num_experts`` the router's width."""
    return KimiLinearDecoder(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        full_attn_layers=tuple(sizes["full_attn_layers"]),
        first_k_dense_replace=sizes["first_k_dense_replace"],
        ffn_hidden_size=sizes["intermediate_size"],
        moe_ffn_hidden_size=sizes["moe_intermediate_size"],
        num_experts=sizes["router_num_experts"],
        experts_held=sizes["num_experts"],
        top_k=sizes["num_experts_per_token"],
        expert_offset=sizes["expert_offset"],
        routed_scale=float(sizes["routed_scaling_factor"]),
        shared_ffn_hidden_size=(sizes["moe_intermediate_size"]
                                * sizes["num_shared_experts"]),
        kda_head_dim=sizes["kda_head_dim"],
        conv_size=sizes["short_conv_kernel_size"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], rms_norm_eps=sizes["rms_norm_eps"],
        dtype=dtype)


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--layers", type=int, default=5)
    p.add_argument("--experts-held", type=int, default=0,
                   help="experts computed here; default 8 (4 with --cpu)")
    p.add_argument("--batch-size", type=int, default=0,
                   help="default 1 (2 with --cpu)")
    p.add_argument("--seq-len", type=int, default=0,
                   help="default 4096 (64 with --cpu)")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU backend, "
                        "hidden 64, 2 heads of 32, 4 of 16 experts, "
                        "top-2, b2 s64")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes (hidden 64, 2 heads "
              "of 32, 4 of 16 experts, b2 s64 unless given)")
    sizes = dict(CPU_PROXY if args.cpu else PUBLISHED,
                 num_hidden_layers=args.layers)
    if args.experts_held:
        sizes["num_experts"] = args.experts_held
    batch = args.batch_size or (2 if args.cpu else 1)
    seq = args.seq_len or (64 if args.cpu else 4096)
    model = build_model(sizes)
    print(f"apex_tpu {apex_tpu.__version__}: hybrid linear-attention "
          f"expert decoder L{model.num_layers} h{model.hidden_size} "
          f"{model.experts_held}/{model.num_experts} experts top-"
          f"{model.top_k} + shared, amp O2 b{batch} s{seq} on "
          f"{jax.default_backend()}")

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
