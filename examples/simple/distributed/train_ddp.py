"""Distributed training example (port of the reference's
examples/simple/distributed/distributed_data_parallel.py: DDP +
SyncBatchNorm over the device mesh — the reference launches one process
per GPU with torch.distributed.launch; on TPU one process drives the
whole mesh via SPMD).

Run on any topology; on CPU force a virtual mesh first:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/simple/distributed/train_ddp.py
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import comm
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import (
    DistributedDataParallel,
    SyncBatchNorm,
    convert_syncbn_model,
)


class SmallNet(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        h = nn.Conv(16, (3, 3))(x)
        h = SyncBatchNorm(num_features=16, channel_last=True)(
            h, use_running_average=not train)
        h = nn.relu(h)
        h = h.mean(axis=(1, 2))
        return nn.Dense(10)(h)


def main():
    n = len(jax.devices())
    comm.initialize(data=n, pipe=1, ctx=1, model=1)
    mesh = comm.mesh()
    print(f"mesh: {n} devices, data axis {mesh.shape['data']}")

    model = SmallNet()
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * n, 8, 8, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (8 * n,), 0, 10)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)
    params, bstats = variables["params"], variables["batch_stats"]
    opt = FusedSGD(params, lr=0.1, momentum=0.9)
    ddp = DistributedDataParallel(model.apply)

    def step_shard(p, bs, xs, ys):
        """Runs per-shard under shard_map: local fwd/bwd, DDP's psum."""
        def loss_fn(pp):
            out, upd = ddp(
                {"params": pp, "batch_stats": bs}, xs, train=True,
                mutable=["batch_stats"])
            logp = jax.nn.log_softmax(out.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                logp, ys[:, None], axis=1)), upd["batch_stats"]
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        grads = ddp.reduce_gradients(grads)      # bucketed allreduce ≙ psum
        loss = jax.lax.pmean(loss, "data")
        new_bs = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "data"), new_bs)
        return loss, grads, new_bs

    jstep = jax.jit(comm.shard_map(
        step_shard, mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P())))

    losses = []
    for i in range(30):
        loss, grads, bstats = jstep(opt.params, bstats, x, y)
        opt.step(grads)
        losses.append(float(loss))
        if i % 10 == 0:
            print(f"step {i:3d} loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print(f"OK: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(SyncBN stats + grads synced over {n} devices)")


if __name__ == "__main__":
    main()
