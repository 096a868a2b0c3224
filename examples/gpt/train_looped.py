"""Looped-decoder pretraining step: amp O2 + FusedAdam (AdamW) with
global-norm clipping, over ``apex_tpu.models.looped.LoopedDecoder`` —
one stack of layers run ``--passes`` times with the same weights, an
exit after every pass.

Synthetic next-token batches (no corpus on disk); the amp plumbing, the
flat-bucket Adam step with masters and the clip folded into it, the
fused cross-entropy and the throughput accounting are the real thing.

Usage:
    python examples/gpt/train_looped.py [--steps 20] [--layers 4]
        [--passes 4] [--batch-size 1] [--seq-len 4096]

Sizes are what the flags say (the 2.6B looped decoder's widths, four
layers, b1 s4096 by default) on whatever backend jax starts; ``--cpu``
is the explicit small proxy the tests run.  ``main(argv)`` returns a
summary dict (losses, found_inf total, step time).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))  # repo-root run, no install

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp
from apex_tpu.models.looped import LoopedDecoder
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry import retrace

WARMUP_STEPS = 2
# the flat Adam step chunked as the BERT example's LAMB step is: one
# bucket of 407 M elements would double the step's temporaries
MAX_BUCKET_BYTES = 128 << 20
MAX_GRAD_NORM = 1.0


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--layers", type=int, default=0, help="default 4")
    p.add_argument("--passes", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=0,
                   help="default 1 (2 with --cpu)")
    p.add_argument("--seq-len", type=int, default=0,
                   help="default 4096 (64 with --cpu)")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU "
                        "backend, hidden 128, 2 layers, b2 s64")
    return p.parse_args(argv)


def build_step(model, amp_state, max_grad_norm=MAX_GRAD_NORM):
    """The jitted forward+backward: (params, scaler, tokens, labels) ->
    (loss, grads, found_inf, clip_coef); ``clip_coef`` is the
    global-norm clip for ``FusedAdam.step(..., clip_coef=)``, which
    folds it into the update (the gradients are never rescaled)."""

    def loss_fn(p, tokens, labels):
        return model.loss({"params": p}, tokens, labels)

    wrapped = amp_state.wrap_forward(loss_fn, cast_argnums=())

    @jax.jit
    def step(p, scaler, tokens, labels):
        loss, grads, found_inf = amp.scaled_value_and_grad(
            wrapped, scaler, p, tokens, labels)
        with jax.named_scope("apex_amp/grad_norm"):
            norm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)))
            clip_coef = jnp.minimum(max_grad_norm / (norm + 1e-6), 1.0)
        return loss, grads, found_inf, clip_coef

    return step


def build_optimizer(params, amp_state, **hypers):
    """FusedAdam (AdamW) over amp O2's params with float32 masters.
    Returns (optimizer, amp_state): the optimizer packs its own copy of
    the masters, so amp's are dropped — kept, they are a second 4 bytes
    a parameter for as long as the state lives."""
    opt = FusedAdam(params, adam_w_mode=True, master_weights=True,
                    masters=amp_state.master_params,
                    max_bucket_bytes=MAX_BUCKET_BYTES, **hypers)
    return opt, dataclasses.replace(amp_state, master_params=None)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes (hidden 128, "
              "2 layers, b2 s64 unless given)")
    batch = args.batch_size or (2 if args.cpu else 1)
    seq = args.seq_len or (64 if args.cpu else 4096)
    if args.cpu:
        model = LoopedDecoder(vocab_size=2048, hidden_size=128, num_heads=4,
                              num_layers=args.layers or 2,
                              ffn_hidden_size=352, num_passes=args.passes,
                              dtype=jnp.bfloat16)
    else:
        model = LoopedDecoder(vocab_size=49152, hidden_size=2048,
                              num_heads=16, num_layers=args.layers or 4,
                              ffn_hidden_size=5632, num_passes=args.passes,
                              dtype=jnp.bfloat16)
    print(f"apex_tpu {apex_tpu.__version__}: looped decoder "
          f"L{model.num_layers} x{model.num_passes} h{model.hidden_size} "
          f"amp O2 b{batch} s{seq} on {jax.default_backend()}")

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
        loss, grads, found_inf, clip_coef = step(
            opt.params, amp_state.scaler, tokens, labels)
        opt.step(grads, found_inf=found_inf, clip_coef=clip_coef)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(loss)
        infs.append(found_inf)
        # what set-up was made of, once: at the first step during which
        # nothing was traced or loaded (docs/observability.md)
        told = retrace.process().report_once()
        if told:
            print(told)
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
               "timed_steps": max(timed, 0), "step_ms": None}
    if timed > 0:
        dt = (time.perf_counter() - t0) / timed
        summary["step_ms"] = dt * 1e3
        print(f"step time {dt*1e3:.1f} ms  "
              f"({batch * seq / dt:.0f} tokens/sec)")
    return summary


if __name__ == "__main__":
    main()
