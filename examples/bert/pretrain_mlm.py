"""BERT MLM pretraining step — BASELINE config 3: FusedLAMB +
FusedLayerNorm + contrib.xentropy (reference recipe: BERT-Large
pretraining with apex's LAMB, the second tracked metric).

Synthetic masked-LM batches (no corpus on disk); the amp plumbing,
LAMB step with masters, fused cross-entropy, and throughput accounting
are the real thing.

Usage:
    python examples/bert/pretrain_mlm.py [--large] [--steps 20]
        [--batch-size 8] [--seq-len 512] [--opt-level O2]

Sizes are what the flags say (b8 s512 by default) on whatever backend
jax starts; ``--cpu`` is the explicit small proxy the tests run.
``main(argv)`` returns a summary dict (losses, found_inf total, loss
scales, step time, compilations inside the timed steps, the jitted
step and the optimizer) — ``chip_smoke.py`` drives the example
through it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))  # repo-root run, no install

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models.bert import BertModel, bert_large
from apex_tpu.optimizers import FusedLAMB
from apex_tpu.telemetry import retrace as startup
from apex_tpu.telemetry.retrace import BACKEND_COMPILE_EVENT, RetraceCounter

# untimed leading steps: the first compiles the programs, the second
# absorbs any second-call variant (committed-vs-fresh input layouts)
WARMUP_STEPS = 2
# FusedLAMB's flat buckets are chunked: BERT-Large as ONE 334 M-element
# bucket needs ~10 GB of LAMB temporaries beside 8.7 GB of arguments
# and outputs, which a 16 GB chip does not have (PR 21's compile report);
# at 128 MiB (11 buckets) the whole example peaks at 12.3 GB
MAX_BUCKET_BYTES = 128 << 20


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--large", action="store_true",
                   help="BERT-Large (default: a 4-layer hidden-128 "
                        "proxy)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=0,
                   help="default 8 (2 with --cpu)")
    p.add_argument("--seq-len", type=int, default=0,
                   help="default 512 (64 with --cpu)")
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU "
                        "backend, b2 s64 unless given")
    p.add_argument("--packed", action="store_true",
                   help="pack a varlen synthetic corpus into fixed "
                        "rows (apex_tpu.data.pack_sequences): "
                        "segment-masked attention, per-sequence "
                        "positions, padding excluded from the loss")
    p.add_argument("--offload-state", action="store_true",
                   help="keep LAMB state in pinned host memory "
                        "(apex_tpu.offload)")
    return p.parse_args(argv)


def build_step(model, amp_state):
    """The jitted forward+backward: (params, scaler, tokens, labels
    [, segment_ids, positions]) -> (loss, grads, found_inf)."""
    vocab = model.vocab_size

    def loss_fn(p, tokens, labels, segment_ids=None, positions=None):
        logits = model.mlm_logits({"params": p}, tokens,
                                  segment_ids=segment_ids,
                                  positions=positions)     # (s,b,V) f32
        flat = logits.transpose(1, 0, 2).reshape(-1, vocab)
        # padding_idx labels (-1 on packed padding) drop out of the CE
        losses = softmax_cross_entropy_loss(
            flat, labels.reshape(-1), smoothing=0.0, padding_idx=-1)
        n = jnp.maximum(jnp.sum(labels.reshape(-1) != -1), 1)
        return jnp.sum(losses) / n

    wrapped = amp_state.wrap_forward(loss_fn, cast_argnums=())

    @jax.jit
    def step(p, scaler, tokens, labels, segment_ids=None,
             positions=None):
        return amp.scaled_value_and_grad(wrapped, scaler, p, tokens,
                                         labels,
                                         segment_ids=segment_ids,
                                         positions=positions)

    return step


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes "
              "(b2 s64 unless given)")
    batch = args.batch_size or (2 if args.cpu else 8)
    seq = args.seq_len or (64 if args.cpu else 512)
    half = jnp.bfloat16 if args.opt_level != "O0" else jnp.float32
    if args.large:
        model = bert_large(dtype=half, max_seq_len=max(seq, 512))
    else:
        model = BertModel(vocab_size=2048, hidden_size=128, num_heads=4,
                          num_layers=4, max_seq_len=max(seq, 128),
                          dtype=half)
    vocab = model.vocab_size
    print(f"apex_tpu {apex_tpu.__version__}: bert "
          f"({'large' if args.large else 'proxy'}) amp {args.opt_level} "
          f"b{batch} s{seq} on {jax.default_backend()}")

    tokens0 = jnp.zeros((batch, seq), jnp.int32)
    params = model.init(jax.random.key(0), tokens0)["params"]
    params, amp_state = amp.initialize(params, opt_level=args.opt_level)
    opt = FusedLAMB(params, lr=args.lr, weight_decay=args.weight_decay,
                    master_weights=bool(amp_state.properties.master_weights),
                    masters=amp_state.master_params,
                    max_bucket_bytes=MAX_BUCKET_BYTES,
                    offload_state=args.offload_state)

    step = build_step(model, amp_state)

    # ONE fixed synthetic batch: overfitting it makes the descent
    # visible (fresh random labels would just sit at uniform entropy)
    pack_kw = {}
    if args.packed:
        import numpy as _np

        from apex_tpu.data import pack_sequences
        rng = _np.random.default_rng(1)
        lens = rng.integers(seq // 4, seq, size=2 * batch)
        packed = pack_sequences(
            [rng.integers(1, vocab, size=n) for n in lens], max_len=seq)
        tokens = jnp.asarray(packed["tokens"])[:batch]
        segs = _np.asarray(packed["segment_ids"])[:batch]
        labels = _np.array(rng.integers(0, vocab,
                                        size=tokens.shape))
        labels[segs == 0] = -1           # padding out of the loss
        labels = jnp.asarray(labels)
        pack_kw = {"segment_ids": jnp.asarray(segs),
                   "positions": jnp.asarray(
                       packed["positions"])[:batch]}
        frac = float((segs > 0).mean())
        kept = sum(len(_np.unique(r[r > 0])) for r in segs)
        print(f"packed: kept {kept} of {len(lens)} varlen seqs in "
              f"{tokens.shape[0]} rows, {frac:.0%} tokens real")
    else:
        tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                    vocab)
        labels = jax.random.randint(jax.random.key(2), (batch, seq), 0,
                                    vocab)
    retrace = RetraceCounter()
    retrace.install()
    scale0 = amp_state.scaler.loss_scale
    losses, infs = [], []
    t0 = compiles0 = None
    for i in range(args.steps):
        loss, grads, found_inf = step(opt.params, amp_state.scaler,
                                      tokens, labels, **pack_kw)
        # branch-free overflow skip: the flag stays on device (the old
        # `if int(found_inf) == 0` gate synced the host every step)
        opt.step(grads, found_inf=found_inf)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(loss)
        infs.append(found_inf)
        # what set-up was made of, once: at the first step during which
        # nothing was traced or loaded (docs/observability.md)
        told = startup.process().report_once()
        if told:
            print(told)
        if i == WARMUP_STEPS - 1:
            jax.block_until_ready((loss, opt.params))
            compiles0 = retrace.events[BACKEND_COMPILE_EVENT]
            t0 = time.perf_counter()
        if i % 5 == 0:
            # 1-in-5-steps console echo, not a per-step sync
            print(f"step {i:3d} loss {float(loss):.4f} "   # apexlint: disable=APX102
                  f"scale {float(amp_state.scaler.loss_scale):.0f}")   # apexlint: disable=APX102
    jax.block_until_ready(opt.params)
    timed = args.steps - WARMUP_STEPS
    summary = {
        "losses": [float(x) for x in losses],
        "found_inf": sum(int(x) for x in infs),
        "loss_scale": (float(scale0),
                       float(amp_state.scaler.loss_scale)),
        "timed_steps": max(timed, 0), "step_ms": None,
        "compiles_in_timed_steps": None,
        # the jitted forward+backward with one call's arguments, and
        # the optimizer with its last inputs: enough to lower either
        # program again for inspection
        "train_step": (step, (opt.params, amp_state.scaler, tokens,
                              labels), pack_kw),
        "optimizer": opt, "last_grads": (grads, found_inf),
    }
    if timed > 0:
        dt = (time.perf_counter() - t0) / timed
        summary["step_ms"] = dt * 1e3
        summary["compiles_in_timed_steps"] = (
            retrace.events[BACKEND_COMPILE_EVENT] - compiles0)
        # packed rows contain padding: count REAL tokens only, so the
        # packed and unpacked numbers compare honestly
        real = tokens.shape[0] * seq * (frac if args.packed else 1.0)
        print(f"step time {dt*1e3:.1f} ms  "
              f"({real/dt:.0f} tokens/sec)  compilations inside the "
              f"{timed} timed steps: "
              f"{summary['compiles_in_timed_steps']}")
    retrace.uninstall()
    return summary


if __name__ == "__main__":
    main()
