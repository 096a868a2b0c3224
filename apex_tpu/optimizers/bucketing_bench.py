"""Per-leaf vs bucketed optimizer-step microbench.

The point of the bucketed flat path is amortizing per-leaf dispatch: a
ResNet-50/BERT-sized pytree is hundreds of small XLA ops per step on
the per-leaf path versus one flat Pallas kernel per dtype bucket.  This
module times both paths over the SAME many-leaf pytree with benchlib's
amortized on-device loop (one dispatch runs many steps serially, so a
measurement times the program, not the dispatch).

``bench_amp_pipeline`` extends the comparison to the FULL amp gradient
side of a train step (unscale + finite check + global-norm clip +
optimizer update): per-leaf amp ops vs the flat pipeline's pack-once /
fused-kernel-per-bucket chain (amp/flat_pipeline.py).

Shared by bench.py (TPU extras), tools/kernel_bench.py (JSON row) and
the tier-1 smoke test (tiny shapes, CPU: proves the harness, not
performance).
"""

from __future__ import annotations


def many_leaf_params(jax, jnp, layers: int = 48, hidden: int = 256):
    """A transformer-ish pytree: per layer one square matrix plus three
    small vectors — the shape mix (few big, many tiny leaves) where
    per-leaf stepping drowns in dispatch."""
    keys = jax.random.split(jax.random.key(0), layers)
    return {
        f"layer{i:03d}": {
            "w": jax.random.normal(keys[i], (hidden, hidden), jnp.float32),
            "b": jnp.zeros((hidden,), jnp.float32),
            "scale": jnp.ones((hidden,), jnp.float32),
            "shift": jnp.zeros((hidden,), jnp.float32),
        }
        for i in range(layers)
    }


def many_leaf_loss(jnp):
    """The loss over a :func:`many_leaf_params` tree (tanh stack with
    scale/shift), shared so every consumer measures the SAME model:
    bench_grad_accum's train legs ground the perf-budget row
    (grad_accum_n8_speedup) that tools/autotune.py restamps, and the
    autotuner's pipeline-chunk sweep must not drift onto a different
    toy network."""
    def loss_fn(p, x):
        h = x
        for k in sorted(p):
            h = jnp.tanh(h @ p[k]["w"] + p[k]["b"]) \
                * p[k]["scale"] + p[k]["shift"]
        return jnp.mean(h ** 2)
    return loss_fn


def bench_optimizer_bucketing(layers: int = 48, hidden: int = 256,
                              iters: int = 10, reps: int = 3,
                              optimizer: str = "adam"):
    """Times one optimizer step, per-leaf vs bucketed, on a many-leaf
    pytree.  Returns a dict of ms timings plus the speedup and the
    bucket plan summary."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.benchlib import timeit
    from apex_tpu.optimizers import FusedAdam, FusedLAMB, FusedSGD

    cls = {"adam": FusedAdam, "sgd": FusedSGD, "lamb": FusedLAMB}[optimizer]
    params = many_leaf_params(jax, jnp, layers, hidden)
    grads = jax.tree_util.tree_map(lambda p: p * 1e-3 + 1e-4, params)

    out = {
        "optim": optimizer,
        "optim_leaves": len(jax.tree_util.tree_leaves(params)),
        "optim_elements": sum(int(l.size) for l in
                              jax.tree_util.tree_leaves(params)),
    }
    for fuse, label in ((False, "perleaf"), (True, "bucketed")):
        opt = cls(params, lr=1e-3, fuse_buckets=fuse)
        if fuse:
            out["optim_buckets"] = opt._plan.describe()
            args = (opt._param_bufs, None, opt.opt_state)
        else:
            args = (opt.params, None, opt.opt_state)
        hypers = {k: jnp.asarray(v, jnp.float32)
                  for k, v in opt.hypers.items()
                  if isinstance(v, float)}
        # the pure step body (what a train loop embeds); jitted fresh ON
        # PURPOSE: the loop has exactly two iterations (per-leaf vs
        # bucketed are different programs), not a hot path
        # apexlint: disable-next=APX302
        step_fn = jax.jit(opt._full_step_impl)
        ms = timeit(step_fn, *args, grads, jnp.int32(2),
                    jnp.float32(1.0), hypers, iters=iters, reps=reps)
        out[f"optim_step_{label}_ms"] = round(ms, 3)
    if out["optim_step_bucketed_ms"]:
        out["optim_bucketing_speedup"] = round(
            out["optim_step_perleaf_ms"] / out["optim_step_bucketed_ms"], 2)
    return out


def bench_amp_pipeline(layers: int = 48, hidden: int = 256,
                       iters: int = 10, reps: int = 3,
                       max_grad_norm: float = 1.0):
    """Full AMP gradient epilogue, per-leaf vs flat, same grads.

    Per-leaf: ``check_finite`` + ``unscale_grads`` + ``clip_grad_norm``
    + per-leaf fused-Adam step — 3 full pytree walks plus the ravel
    clip_grad does, then per-leaf update math.  Flat: ONE pack,
    ``flat_unscale_norm`` per bucket (unscale + flag + Σg² in one HBM
    read), clip coefficient folded into the flat Adam kernels' grad
    scaling.  Grads are precomputed (identical input to both paths) so
    the number isolates the gradient pipeline, not the backward."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.benchlib import timeit
    from apex_tpu.contrib.clip_grad import clip_grad_norm
    from apex_tpu.optimizers import FusedAdam

    params = many_leaf_params(jax, jnp, layers, hidden)
    scaler = amp.LossScaleState.create(2.0 ** 12)
    scale = float(scaler.loss_scale)
    grads = jax.tree_util.tree_map(
        lambda p: (p * 1e-3 + 1e-4) * scale, params)   # "scaled" grads

    out = {
        "amp_leaves": len(jax.tree_util.tree_leaves(params)),
        "amp_elements": sum(int(l.size) for l in
                            jax.tree_util.tree_leaves(params)),
        "amp_max_grad_norm": max_grad_norm,
    }

    # --- per-leaf oracle path -------------------------------------------
    opt_pl = FusedAdam(params, lr=1e-3, fuse_buckets=False)

    def per_leaf_step(work, opt_state, grads, scaler_state, step):
        found_inf = amp.check_finite(grads)
        g = amp.unscale_grads(grads, scaler_state)
        g, _norm = clip_grad_norm(g, max_grad_norm)
        new_work, new_state = opt_pl.functional_step(
            work, opt_state, g, step)
        return new_work, new_state, found_inf

    # --- flat pipeline path ---------------------------------------------
    opt_fl = FusedAdam(params, lr=1e-3, fuse_buckets=True)
    pipe = amp.FlatGradPipeline(optimizer=opt_fl,
                                max_grad_norm=max_grad_norm)

    def flat_step(work, opt_state, grads, scaler_state, step):
        flat = pipe.unscale_and_norm(pipe.pack(grads), scaler_state)
        new_work, new_state = opt_fl.functional_step(
            work, opt_state, flat.bufs, step, clip_coef=flat.clip_coef)
        return new_work, new_state, flat.found_inf

    for label, fn, opt in (("per_leaf", per_leaf_step, opt_pl),
                           ("flat", flat_step, opt_fl)):
        # two programs, two compiles — not a hot-loop retrace
        # apexlint: disable-next=APX302
        step_fn = jax.jit(fn)
        ms = timeit(step_fn, params, opt.opt_state, grads, scaler,
                    jnp.int32(2), iters=iters, reps=reps)
        out[f"amp_step_{label}_ms"] = round(ms, 3)
    if out["amp_step_flat_ms"]:
        out["amp_pipeline_speedup"] = round(
            out["amp_step_per_leaf_ms"] / out["amp_step_flat_ms"], 2)
    return out


def bench_flat_accumulate(layers: int = 48, hidden: int = 256,
                          iters: int = 10, reps: int = 3):
    """One microbatch accumulation, per-leaf tree-map-add vs fused
    flat: the loop body a grad-accumulation train step pays N_micro
    times per step.  Per-leaf: one XLA add per leaf (hundreds of tiny
    dispatches on a transformer tree) into a per-leaf f32 accumulator
    tree.  Flat: grads arrive PACKED (the pipeline's reality — packed
    once at the backward) and ``flat_accumulate`` does one fused
    read-modify-write per dtype bucket with the found_inf latch from
    the same HBM sweep.  The per-leaf side gets its latch the per-leaf
    way (``check_finite``), so both sides answer the same question.
    Beside the two timings, ``accum_*_ops`` count the equations each
    side traces to per accumulation: what the layout decides, whatever
    the machine's load does to a clock."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.benchlib import timeit
    from apex_tpu.optimizers import FusedAdam

    params = many_leaf_params(jax, jnp, layers, hidden)
    grads = jax.tree_util.tree_map(lambda p: p * 1e-3 + 1e-4, params)

    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt)
    acc_flat = opt.grad_accum_init()
    acc_tree = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    packed = opt._plan.pack_grads(grads)

    def per_leaf(acc, grads, bad):
        new = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return new, jnp.maximum(bad, amp.check_finite(new))

    def flat(acc, bufs):
        return pipe.accumulate(acc, bufs)

    out = {
        "accum_leaves": len(jax.tree_util.tree_leaves(params)),
        "accum_elements": sum(int(l.size) for l in
                              jax.tree_util.tree_leaves(params)),
    }
    out["accum_per_leaf_ops"] = len(jax.make_jaxpr(per_leaf)(
        acc_tree, grads, jnp.int32(0)).eqns)
    out["accum_flat_ops"] = len(jax.make_jaxpr(flat)(
        acc_flat, packed).eqns)
    # two programs, two compiles — not a hot-loop retrace
    # apexlint: disable-next=APX302
    ms_pl = timeit(jax.jit(per_leaf), acc_tree, grads, jnp.int32(0),
                   iters=iters, reps=reps)
    # apexlint: disable-next=APX302
    ms_fl = timeit(jax.jit(flat), acc_flat, packed,
                   iters=iters, reps=reps)
    out["accum_per_leaf_ms"] = round(ms_pl, 3)
    out["accum_flat_ms"] = round(ms_fl, 3)
    if ms_fl:
        out["accum_flat_speedup"] = round(ms_pl / ms_fl, 2)
    return out


def bench_grad_accum(layers: int = 16, hidden: int = 128,
                     batch: int = 32, n_micro=(1, 4, 8),
                     iters: int = 5, reps: int = 3):
    """Full microbatched AMP train steps, per-leaf vs flat
    accumulation, at N_micro in {1,4,8} (bench.py's grad_accum train
    legs).  Each leg is one jitted step: scaled_value_and_grad with
    ``microbatches=N`` on the respective layout, then the fused (or
    per-leaf) optimizer update with the latched found_inf."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.benchlib import timeit
    from apex_tpu.optimizers import FusedAdam

    params = many_leaf_params(jax, jnp, layers, hidden)
    x = jax.random.normal(jax.random.key(1), (batch, hidden))
    scaler = amp.LossScaleState.create(2.0 ** 12)
    loss_fn = many_leaf_loss(jnp)

    out = {"grad_accum_batch": batch,
           "grad_accum_leaves":
           len(jax.tree_util.tree_leaves(params))}
    opt_fl = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt_fl)
    opt_pl = FusedAdam(params, lr=1e-3, fuse_buckets=False)
    hypers = {k: jnp.asarray(v, jnp.float32)
              for k, v in opt_fl.hypers.items()
              if isinstance(v, float)}
    for n in n_micro:
        def flat_step(work, opt_state, x, step, n=n):
            ptree = pipe.plan.unpack(work)
            loss, flat = pipe.scaled_value_and_grad(
                loss_fn, scaler, ptree, x, microbatches=n)
            new_w, _, new_s = opt_fl._full_step_flat(
                work, None, opt_state, flat.bufs, step, 1.0,
                hypers, flat.found_inf)
            return loss, new_w, new_s

        def per_leaf_step(work, opt_state, x, step, n=n):
            loss, grads, found = amp.scaled_value_and_grad(
                loss_fn, scaler, work, x, microbatches=n)
            new_w, new_s = opt_pl.functional_step(
                work, opt_state, grads, step, found_inf=found)
            return loss, new_w, new_s

        # each (layout, N) pair is its own program by design (not a
        # hot-loop retrace), and the bench reruns one program many
        # times over the SAME state arrays — donating opt_state would
        # delete the inputs after the first rep
        # apexlint: disable-next=APX302
        ms_fl = timeit(jax.jit(flat_step), opt_fl._param_bufs,   # apexlint: disable=APX401
                       opt_fl.opt_state, x, jnp.int32(2),
                       iters=iters, reps=reps)
        # apexlint: disable-next=APX302
        ms_pl = timeit(jax.jit(per_leaf_step), params,   # apexlint: disable=APX401
                       opt_pl.opt_state, x, jnp.int32(2),
                       iters=iters, reps=reps)
        out[f"grad_accum_flat_n{n}_ms"] = round(ms_fl, 3)
        out[f"grad_accum_per_leaf_n{n}_ms"] = round(ms_pl, 3)
        if ms_fl:
            out[f"grad_accum_n{n}_speedup"] = round(ms_pl / ms_fl, 2)
    return out


def mixed_dtype_params(jax, jnp, layers: int = 48, hidden: int = 256):
    """The many-leaf tree in amp-O2 clothing: bf16 matmul weights plus
    f32 norm vectors per layer — two dtype buckets, masters for the
    bf16 leaves, the state mix a real checkpoint carries."""
    base = many_leaf_params(jax, jnp, layers, hidden)
    return {
        name: {"w": leaves["w"].astype(jnp.bfloat16), "b": leaves["b"],
               "scale": leaves["scale"], "shift": leaves["shift"]}
        for name, leaves in base.items()
    }


def bench_checkpoint_snapshot(layers: int = 48, hidden: int = 256,
                              reps: int = 5):
    """Training-state snapshot+serialize time, bucket-native (v2) vs
    per-leaf (v1), over the same realistic mixed-dtype tree.

    Each rep is one full ``save_training_state`` to a scratch file:
    snapshot (device copies / per-leaf state_dict walk), device->host
    transfer, checksum, header and the sequential write.  This is a
    HOST path — disk and PCIe, not a jittable device program — so it
    is timed by wall-clock median over reps (the telemetry_flush_ms
    idiom), not benchlib's on-device loop; the file lands in a tmpdir
    so the numbers include real filesystem work."""
    import os
    import shutil
    import statistics
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu import checkpoint as ckpt
    from apex_tpu.optimizers import FusedAdam

    params = mixed_dtype_params(jax, jnp, layers, hidden)
    grads = jax.tree_util.tree_map(
        lambda p: (p * 1e-3 + 1e-4).astype(p.dtype), params)

    tmpdir = tempfile.mkdtemp(prefix="apex_ckpt_bench_")
    out = {
        "ckpt_leaves": len(jax.tree_util.tree_leaves(params)),
        "ckpt_elements": sum(int(l.size) for l in
                             jax.tree_util.tree_leaves(params)),
    }
    try:
        for fuse, fmt, label in ((True, "v2", "bucketed"),
                                 (False, "v1", "perleaf")):
            opt = FusedAdam(params, lr=1e-3, fuse_buckets=fuse)
            opt.step(grads)            # realistic non-zero opt state
            path = os.path.join(tmpdir, f"snap_{label}.ckpt")
            ms = []
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                ckpt.save_training_state(path, optimizer=opt,
                                         step=1, format=fmt)
                ms.append((time.perf_counter() - t0) * 1e3)
            out[f"ckpt_snapshot_{label}_ms"] = round(
                statistics.median(ms), 3)
            out[f"ckpt_bytes_{label}"] = os.path.getsize(path)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    if out["ckpt_snapshot_bucketed_ms"]:
        out["ckpt_snapshot_speedup"] = round(
            out["ckpt_snapshot_perleaf_ms"]
            / out["ckpt_snapshot_bucketed_ms"], 2)
    return out
