"""ImageNet AMP training (port of the reference's
examples/imagenet/main_amp.py — the north-star config of BASELINE.md:
ResNet-50, amp O2, FusedSGD).

No ImageNet on disk in this environment, so data is synthetic
ImageNet-shaped batches (the training math, amp plumbing, checkpoint
bundle, and throughput accounting are the real thing).

Usage:
    python examples/imagenet/main_amp.py --arch resnet50 --opt-level O2
        [--batch-size 128] [--steps 100] [--ddp] [--sync-bn]
        [--checkpoint PATH]

Sizes are what the flags say (b128, 224 px by default) on whatever
backend jax starts; ``--cpu`` is the explicit small proxy the tests
run.  ``main(argv)`` returns a summary dict (losses, found_inf total,
loss scales, step time, compilations inside the timed steps, the
jitted step and the optimizer) — ``chip_smoke.py`` drives the example
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
from apex_tpu import amp, checkpoint, comm
from apex_tpu.models import resnet18, resnet34, resnet50, resnet101
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import DistributedDataParallel
from apex_tpu.telemetry.retrace import BACKEND_COMPILE_EVENT, RetraceCounter

# untimed leading steps: the first compiles the programs, the second
# compiles FusedSGD's post-first_run variant of the update
WARMUP_STEPS = 2

ARCHS = {"resnet18": resnet18, "resnet34": resnet34,
         "resnet50": resnet50, "resnet101": resnet101}


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--batch-size", type=int, default=0,
                   help="default 128 (8 with --cpu)")
    p.add_argument("--image-size", type=int, default=0,
                   help="default 224 (64 with --cpu)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--ddp", action="store_true",
                   help="data-parallel over the mesh 'data' axis")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatch gradient accumulation: split each "
                        "batch into N microbatches and accumulate "
                        "FLAT (amp.scaled_value_and_grad's "
                        "microbatches= path — one fused add per "
                        "bucket per microbatch, found_inf latched, "
                        "never a per-leaf gradient tree)")
    p.add_argument("--sync-bn", action="store_true",
                   help="convert BatchNorm to SyncBatchNorm over the "
                        "'data' mesh axis (reference: --sync_bn + "
                        "apex.parallel.convert_syncbn_model)")
    p.add_argument("--checkpoint", default="",
                   help="single-file checkpoint bundle (load + final "
                        "save; the legacy path)")
    p.add_argument("--checkpoint-dir", default="",
                   help="rotating crash-safe checkpoints via "
                        "resilience.CheckpointManager (bucket-native "
                        "v2, resume-from-newest-valid; overrides "
                        "--checkpoint)")
    p.add_argument("--save-every", type=int, default=10,
                   help="checkpoint cadence in steps "
                        "(--checkpoint-dir)")
    p.add_argument("--preempt-at-step", type=int, default=None,
                   help="simulate a preemption notice at step N: "
                        "forced final checkpoint, clean exit "
                        "(--checkpoint-dir; SIGTERM does the same)")
    p.add_argument("--cpu", action="store_true",
                   help="the small CPU proxy the tests run: CPU "
                        "backend, b8 64 px unless given")
    p.add_argument("--stem-space-to-depth", action="store_true",
                   help="MXU-efficient stem: compute the 7x7/s2 stem "
                        "conv as a 4x4/s1 conv over space-to-depth "
                        "input (same function, pinned by tests; the "
                        "MXU sees 12 input channels instead of 3 — "
                        "the MLPerf TPU ResNet transform bench.py "
                        "uses on hardware)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        from apex_tpu.platform import select_platform
        select_platform("cpu")
        print("--cpu: CPU backend, small proxy sizes "
              "(b8 64 px unless given)")
    batch = args.batch_size or (8 if args.cpu else 128)
    size = args.image_size or (64 if args.cpu else 224)
    accum_note = (f" grad-accum {args.grad_accum} (flat)"
                  if args.grad_accum > 1 else "")
    print(f"apex_tpu {apex_tpu.__version__}: {args.arch} "
          f"amp {args.opt_level} batch {batch} img {size} "
          f"on {jax.default_backend()}{accum_note}")

    kwargs = dict(num_classes=1000)
    if args.stem_space_to_depth:
        kwargs["stem_space_to_depth"] = True
    if args.sync_bn:
        # reference: apex.parallel.convert_syncbn_model(model); here the
        # model takes the norm class directly
        import functools
        from apex_tpu.parallel import SyncBatchNorm
        kwargs["norm_cls"] = functools.partial(
            SyncBatchNorm, channel_last=True,
            process_group=comm.AXIS_DATA)
    model = ARCHS[args.arch](**kwargs)
    x0 = jnp.zeros((batch, size, size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x0, train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    params, amp_state = amp.initialize(params, opt_level=args.opt_level)
    opt = FusedSGD(params, lr=args.lr, momentum=args.momentum,
                   weight_decay=args.weight_decay,
                   master_weights=bool(amp_state.properties.master_weights),
                   masters=amp_state.master_params)

    ddp = DistributedDataParallel() if args.ddp else None
    if args.ddp and not comm.is_initialized():
        n = len(jax.devices())
        comm.initialize(data=n, pipe=1, ctx=1, model=1)

    def loss_fn(p, bs, x, y):
        out, updates = model.apply(
            {"params": p, "batch_stats": bs}, x,
            train=True, mutable=["batch_stats"])
        logits = out.astype(jnp.float32)
        ll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                  y[:, None], axis=1)
        return jnp.mean(ll), updates["batch_stats"]

    # the amp mechanism does ALL precision work: O1 rewrites the ops of
    # the unmodified model, O2/O3 cast the data input (arg 2)
    wrapped_loss = amp_state.wrap_forward(loss_fn, cast_argnums=(2,))

    if args.grad_accum > 1:
        # fused flat accumulation (replaces the hand-rolled per-leaf
        # accumulation loop): each microbatch's packed grads add into
        # persistent f32 accumulator buckets in one read-modify-write
        # per bucket, the reduce+unscale+clip run ONCE at finalize,
        # and one bad microbatch skips the whole step branch-free
        pipe = amp_state.flat_pipeline(optimizer=opt)

        def train_step(p, bs, scaler, x, y):
            def loss_bs(pp, xx, yy):
                # batch_stats close over: only the BATCH args split
                return wrapped_loss(pp, bs, xx, yy)

            (loss, new_bs), flat = pipe.scaled_value_and_grad(
                loss_bs, scaler, p, x, y, has_aux=True,
                microbatches=args.grad_accum)
            # every microbatch folds BN stats from the same input
            # stats, so the stacked aux holds N independent one-fold
            # candidates; averaging them integrates every
            # microbatch's statistics (mean of micro-means == the
            # full-batch mean) instead of discarding N-1 folds
            new_bs = jax.tree_util.tree_map(
                lambda a: jnp.mean(a, axis=0), new_bs)
            return loss, flat, new_bs, flat.found_inf
    else:
        def train_step(p, bs, scaler, x, y):
            (loss, new_bs), grads, found_inf = \
                amp.scaled_value_and_grad(
                    wrapped_loss, scaler, p, bs, x, y, has_aux=True)
            if ddp is not None:
                grads = ddp.reduce_gradients(grads)
            return loss, grads, new_bs, found_inf

    if args.ddp:
        jstep = jax.jit(
            train_step,
            in_shardings=(None, None, None,
                          comm.sharding("data"), comm.sharding("data")))
    else:
        jstep = jax.jit(train_step)

    step0 = 0
    mgr = guard = None
    if args.checkpoint_dir:
        # the resilient save path: rotating bucket-native checkpoints,
        # resume-from-newest-valid, SIGTERM -> final-save-then-exit
        from apex_tpu.resilience import (CheckpointManager,
                                         PreemptionGuard)
        mgr = CheckpointManager(args.checkpoint_dir, keep=3,
                                every=args.save_every)
        guard = PreemptionGuard(
            preempt_at_step=args.preempt_at_step).install()
        out = mgr.restore_latest(opt.params, opt,
                                 extra_like=batch_stats)
        if out is not None:
            _, amp_sd, step0, batch_stats = out
            if amp_sd:
                amp_state = amp_state.load_state_dict(amp_sd)
            print(f"resumed at step {step0} "
                  f"scale {float(amp_state.scaler.loss_scale):.0f}")
    elif args.checkpoint:
        if os.path.exists(args.checkpoint):
            p_, amp_sd, step0, batch_stats = \
                checkpoint.load_training_state(
                    args.checkpoint, opt.params, opt,
                    extra_like=batch_stats)
            if amp_sd:     # reference: amp.load_state_dict(ckpt['amp'])
                amp_state = amp_state.load_state_dict(amp_sd)
            print(f"resumed at step {step0} "
                  f"scale {float(amp_state.scaler.loss_scale):.0f}")
    # host loader + device prefetcher (reference: the data_prefetcher
    # class in its imagenet example — H2D overlapped with compute; here
    # apex_tpu.data.DevicePrefetcher plays that role, and batches land
    # pre-sharded over the mesh under --ddp)
    import numpy as np
    from apex_tpu.data import DevicePrefetcher

    nrng = np.random.default_rng(1)
    # pre-generate a few host batches and cycle them: keeps the H2D
    # pipeline honest without making single-threaded numpy RNG the
    # bottleneck at TPU batch sizes
    remaining = max(0, args.steps - step0)   # --steps is the TOTAL:
    #                                          a resumed run finishes
    #                                          it, not steps more
    pool = [(nrng.standard_normal(
                 (batch, size, size, 3), dtype=np.float32),
             nrng.integers(0, 1000, (batch,)).astype(np.int32))
            for _ in range(min(4, remaining))]

    prefetcher = DevicePrefetcher(
        (pool[i % len(pool)] for i in range(remaining)), depth=2,
        sharding=comm.sharding("data") if args.ddp else None)

    retrace = RetraceCounter()
    retrace.install()
    scale0 = amp_state.scaler.loss_scale
    losses, infs = [], []
    t0 = compiles0 = None
    done = step0                      # completed steps (1-based count)
    for step, (x, y) in enumerate(prefetcher, start=step0):
        step_args = (opt.params, batch_stats, amp_state.scaler, x, y)
        loss, grads, batch_stats, found_inf = jstep(*step_args)
        # branch-free overflow skip: the flag stays on device (the old
        # `if int(found_inf) == 0` gate synced the host every step)
        opt.step(grads, found_inf=found_inf)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(loss)
        infs.append(found_inf)
        done = step + 1
        if mgr is not None:
            # capture amp state only on cadence steps: state_dict()
            # device_gets the loss scale, and a per-step host sync is
            # the hazard this loop's branch-free skip exists to avoid
            saved_now = mgr.due(done) and mgr.maybe_save(
                done, optimizer=opt, amp_state=amp_state.state_dict(),
                extra=batch_stats)
            if guard.check(done):
                # preemption notice: make this step durable, clean
                # exit — rerun to resume.  A cadence save just
                # scheduled for this step only needs the wait, not a
                # second full write inside the grace window
                if not saved_now:
                    mgr.save(done, optimizer=opt,
                             amp_state=amp_state.state_dict(),
                             extra=batch_stats)
                mgr.wait()
                print(f"preempted: final checkpoint durable at "
                      f"step {done} — rerun to resume")
                break
        if step == step0 + WARMUP_STEPS - 1:
            # every program variant is compiled: time from here
            jax.block_until_ready((loss, opt.params))
            compiles0 = retrace.events[BACKEND_COMPILE_EVENT]
            t0 = time.perf_counter()
        if step % 10 == 0:
            # 1-in-10-steps console echo, not a per-step sync
            print(f"step {step:4d} loss {float(loss):.4f} "   # apexlint: disable=APX102
                  f"scale {float(amp_state.scaler.loss_scale):.0f}")   # apexlint: disable=APX102
    jax.block_until_ready(opt.params)
    preempted = guard is not None and guard.preempted
    n_timed = done - step0 - WARMUP_STEPS   # t0 starts after THIS
    #                                         run's warm-up steps
    summary = {
        "losses": [float(v) for v in losses],
        "found_inf": sum(int(v) for v in infs),
        "loss_scale": (float(scale0),
                       float(amp_state.scaler.loss_scale)),
        "timed_steps": max(n_timed, 0), "step_ms": None,
        "compiles_in_timed_steps": None,
        # the jitted forward+backward with its last call's arguments,
        # and the optimizer with its last inputs: enough to lower
        # either program again for inspection
        "train_step": (jstep, step_args, {}) if losses else None,
        "optimizer": opt,
        "last_grads": (grads, found_inf) if losses else None,
    }
    if t0 and n_timed > 0 and not preempted:
        dt = (time.perf_counter() - t0) / n_timed
        summary["step_ms"] = dt * 1e3
        summary["compiles_in_timed_steps"] = (
            retrace.events[BACKEND_COMPILE_EVENT] - compiles0)
        print(f"throughput {batch / dt:.1f} imgs/sec  "
              f"({dt*1e3:.1f} ms/step)  compilations inside the "
              f"{n_timed} timed steps: "
              f"{summary['compiles_in_timed_steps']}")
    retrace.uninstall()
    if mgr is not None:
        if not preempted:
            mgr.save(done, optimizer=opt,
                     amp_state=amp_state.state_dict(),
                     extra=batch_stats)
            mgr.wait()
            print(f"checkpointed to {args.checkpoint_dir} "
                  f"(step {done})")
        guard.uninstall()
        mgr.close()
    elif args.checkpoint:
        checkpoint.save_training_state(
            args.checkpoint, opt.params, opt,
            amp_state=amp_state.state_dict(),
            step=step0 + args.steps, extra=batch_stats)
        print(f"checkpointed to {args.checkpoint}")
    return summary


if __name__ == "__main__":
    main()
