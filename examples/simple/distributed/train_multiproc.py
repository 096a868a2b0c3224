"""Multi-PROCESS distributed training (the reference's
torch.distributed.launch flow, ported 1:1: N OS processes, env-var
rendezvous, init_process_group, collectives — SURVEY.md §2.6 /
examples/simple/distributed run.sh).

    python -m apex_tpu.launch --nproc 2 \
        examples/simple/distributed/train_multiproc.py

Each worker performs the real `jax.distributed.initialize()` handshake
through `comm.initialize_distributed()` (the init_process_group
analog), builds the GLOBAL mesh, and trains data-parallel: every
process feeds its local shard of the global batch, and under jit the
gradient reduction is a cross-process collective (gloo on CPU, ICI/DCN
on TPU pods — same program).

On TPU pods this file runs unchanged WITHOUT the launcher: the pod
runtime announces itself and initialize_distributed autodetects.
Contrast with train_ddp.py, where ONE process drives the whole mesh
(pure SPMD) — that is the idiomatic single-host TPU shape; this file
is the multi-host / multi-process shape.
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "..", ".."))  # repo-root run

# CPU development default: give each process its own virtual devices
# and never claim a chip from example code run via the launcher (N
# processes on one TPU host would each claim every chip).
if "TPU_WORKER_HOSTNAMES" not in os.environ:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import jax

if "TPU_WORKER_HOSTNAMES" not in os.environ:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from apex_tpu import comm  # noqa: E402
from apex_tpu.optimizers import FusedSGD  # noqa: E402


class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(64)(x)
        x = nn.relu(x)
        return nn.Dense(4)(x)


def main() -> int:
    mesh = comm.initialize_distributed()     # env contract rendezvous
    rank, world = jax.process_index(), jax.process_count()
    n_dev = len(mesh.devices.flatten())
    print(f"[rank {rank}/{world}] global devices: {n_dev}", flush=True)

    model = Net()
    rng = jax.random.key(0)                  # same init on every rank
    x_init = jnp.zeros((2, 16))
    params = model.init(rng, x_init)["params"]
    opt = FusedSGD(params, lr=0.1, momentum=0.9)

    # global batch sharded over every device/process on the data axis;
    # each process materializes ONLY its local rows (the callback asks
    # for global index ranges, and rows are generated per-index — the
    # pattern a real multi-host input pipeline follows)
    batch = 8 * n_dev
    axes = ("data", "pipe", "ctx", "model")

    def x_rows(lo, hi):
        return np.stack([
            np.random.default_rng(100 + r).normal(size=16)
            for r in range(lo, hi)]).astype(np.float32)

    def y_rows(lo, hi):
        xr = x_rows(lo, hi)
        return (xr[:, :4].sum(1) > xr[:, 4:8].sum(1)).astype(np.int32)

    def put(shape, rows_fn):
        spec = P(axes, *([None] * (len(shape) - 1)))

        def cb(idx):
            lo = idx[0].start or 0
            hi = shape[0] if idx[0].stop is None else idx[0].stop
            return rows_fn(lo, hi)

        return jax.make_array_from_callback(
            shape, NamedSharding(mesh, spec), cb)

    x, y = put((batch, 16), x_rows), put((batch,), y_rows)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, i, x, y):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            onehot = jax.nn.one_hot(y, 4)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # grads of replicated params over a sharded batch: GSPMD
        # inserts the cross-process all-reduce (the DDP bucket
        # all-reduce of the reference) automatically
        params, opt_state = opt.functional_step(
            params, opt_state, grads, i)
        return params, opt_state, loss

    l0 = None
    opt_state = opt.opt_state
    for i in range(30):
        params, opt_state, loss = step(params, opt_state,
                                       jnp.float32(i + 1), x, y)
        if l0 is None:
            l0 = float(loss)
    l1 = float(loss)
    print(f"[rank {rank}] loss {l0:.4f} -> {l1:.4f}", flush=True)
    if not (l1 < l0):
        print(f"[rank {rank}] FAIL: loss did not decrease", flush=True)
        return 1
    print(f"[rank {rank}] OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
