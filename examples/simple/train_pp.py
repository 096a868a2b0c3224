"""Pipeline-parallel training with the production 1F1B schedule
(reference: apex/transformer pipeline_parallel usage; SURVEY.md §3.5).

A stack of MLP stages is sharded over the mesh's "pipe" axis and
trained with ``spmd_pipeline_1f1b_apply`` — the differentiable SPMD
pipeline whose backward runs the interleaved one-forward-one-backward
schedule with recompute (O(stages) activation window, independent of
the microbatch count).  Layers before the pipeline (an input
projection) and after it (the head + loss) differentiate straight
through.  Data parallelism rides an outer "data" axis.  Runs on a
virtual 8-device CPU mesh or a real pod unchanged.

``--virtual V`` switches to ``spmd_pipeline_interleaved_1f1b_apply``
with V model chunks per stage (global chunk c*P+s on stage s) — the
reference's interleaved schedule, O(P*V) activation window.
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import comm
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer.pipeline_parallel import spmd

D = 16          # feature width
M = 4           # microbatches
MB = 8          # rows per microbatch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual", type=int, default=0,
                    help="virtual chunks per stage (0: non-interleaved)")
    args = ap.parse_args()
    import os
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # virtual 8-device CPU mesh (must precede first backend use)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    mesh = comm.initialize(data=2, pipe=4)
    pp = comm.pipeline_parallel_size()
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
          f"{jax.default_backend()}")

    k = jax.random.key(0)
    ks = jax.random.split(k, pp + 2)
    # one (D,D) MLP stage per pipe rank, stacked on a leading pipe dim
    # (with --virtual V: V chunks per rank, (pp, V, D, D))
    Vc = args.virtual
    shape = (pp, Vc, D, D) if Vc else (pp, D, D)
    stages = 0.3 * jax.random.normal(ks[0], shape)
    w_in = jnp.eye(D) + 0.05 * jax.random.normal(ks[1], (D, D))
    w_out = 0.3 * jax.random.normal(ks[2], (D, D))
    params = {"in": w_in, "stages": stages, "out": w_out}
    pspec = {"in": P(), "stages": P(comm.AXIS_PIPE), "out": P()}

    # per-leaf state: the shard_map specs below shard each leaf on its
    # own axis (stages on pipe, the rest replicated) — a flat bucket
    # would mix them, so the bucketed packing must stay off here
    opt = FusedAdam(params, lr=3e-3, fuse_buckets=False)

    def stage_fn(w, x):
        return x + jnp.tanh(x @ w)          # residual MLP stage

    def loss_fn(p, x, y):
        ub = x @ p["in"]                    # before the pipeline
        if Vc:
            h = spmd.spmd_pipeline_interleaved_1f1b_apply(
                stage_fn, p["stages"][0], ub)
        else:
            h = spmd.spmd_pipeline_1f1b_apply(
                stage_fn, p["stages"][0], ub)
        out = h @ p["out"]                  # after the pipeline
        return jnp.mean((out - y) ** 2)

    def train_step(p, opt_state, step, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        # Grad conventions across the pipe axis (docs/parallel.md):
        # the pipeline OUTPUT is replicated, so the unmasked loss gives
        # every rank the FULL d loss/d w_out already — summing it again
        # would scale the head gradient by pp.  Only the PRE-pipeline
        # path is partial (the input cotangent emerges on rank 0), so
        # w_in alone needs the psum.
        g = {"in": jax.lax.psum(g["in"], comm.AXIS_PIPE),
             "stages": g["stages"],
             "out": g["out"]}
        # data-parallel mean
        g = jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, comm.AXIS_DATA), g)
        p, opt_state = opt.functional_step(p, opt_state, g, step)
        return p, opt_state, jax.lax.pmean(loss, comm.AXIS_DATA)

    ospec = {"exp_avg": pspec, "exp_avg_sq": pspec}
    step_jit = jax.jit(comm.shard_map(
        train_step, mesh,
        in_specs=(pspec, ospec, P(), P(comm.AXIS_DATA),
                  P(comm.AXIS_DATA)),
        out_specs=(pspec, ospec, P())))

    dp = comm.data_parallel_size()
    x = jax.random.normal(jax.random.key(3), (dp * M, MB, D))
    y = jnp.sin(2.0 * x)

    p, opt_state = opt.params, opt.opt_state
    loss0 = None
    for step in range(1, 61):
        p, opt_state, loss = step_jit(p, opt_state, jnp.int32(step), x, y)
        if step == 1:
            loss0 = float(loss)
        if step % 15 == 0:
            print(f"step {step:3d} loss {float(loss):.4f}")
    final = float(loss)
    assert final < 0.5 * loss0, (loss0, final)
    sched = (f"interleaved-1F1B V={Vc}" if Vc else "1F1B")
    print(f"OK: loss {loss0:.4f} -> {final:.4f} "
          f"(pp={pp}, {sched} backward, dp={dp})")


if __name__ == "__main__":
    main()
