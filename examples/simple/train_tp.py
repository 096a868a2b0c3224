"""Tensor-parallel + data-parallel training with apex_tpu (reference:
examples/simple/distributed) — a Megatron-style TP MLP trained under
shard_map on a data x model mesh, with FusedAdam and amp loss scaling.
Runs on a virtual 8-device CPU mesh or a real pod unchanged.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, comm
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import tensor_parallel as tp


IN, HID = 32, 64


def main():
    mesh = comm.initialize(data=2, model=4)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
          f"{jax.default_backend()}")

    col = tp.ColumnParallelLinear(IN, HID, gather_output=False)
    row = tp.RowParallelLinear(HID, 1, input_is_parallel=True)

    def apply_fn(params, x):
        h = jax.nn.gelu(col.apply(params["col"], x))
        return row.apply(params["row"], h)

    def init_fn(key, x):
        k1, k2 = jax.random.split(key)
        h = jnp.zeros(x.shape[:-1] + (HID // comm.model_parallel_size(),))
        return {"col": col.init(k1, x), "row": row.init(k2, h)}

    pspecs = {
        "col": {"params": {"weight": P(None, comm.AXIS_MODEL),
                           "bias": P(comm.AXIS_MODEL)}},
        "row": {"params": {"weight": P(comm.AXIS_MODEL, None),
                           "bias": P()}},
    }

    x = jax.random.normal(jax.random.key(1), (64, IN))
    y = jnp.sum(x[:, :3], axis=1, keepdims=True)

    params = jax.jit(comm.shard_map(init_fn, mesh, in_specs=(P(), P()),
                               out_specs=pspecs))(jax.random.key(0), x)
    opt = FusedAdam(params, lr=3e-3)
    scaler = amp.LossScaleState.create(1.0)

    def train_step(params, opt_state, scaler, step, xs, ys):
        def loss_fn(p, xs, ys):
            pred = apply_fn(p, xs)
            return jnp.mean((pred - ys) ** 2)

        loss, grads, found_inf = amp.scaled_value_and_grad(
            loss_fn, scaler, params, xs, ys)
        # data-parallel grad mean (DDP semantics)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, comm.AXIS_DATA), grads)
        loss = jax.lax.pmean(loss, comm.AXIS_DATA)
        params, opt_state = opt.functional_step(params, opt_state, grads,
                                                step)
        return params, opt_state, loss

    step_fn = jax.jit(comm.shard_map(
        train_step, mesh,
        in_specs=(pspecs,
                  {"exp_avg": pspecs, "exp_avg_sq": pspecs},
                  P(), P(), P(comm.AXIS_DATA), P(comm.AXIS_DATA)),
        out_specs=(pspecs,
                   {"exp_avg": pspecs, "exp_avg_sq": pspecs},
                   P())))

    opt_state = {"exp_avg": jax.tree_util.tree_map(jnp.zeros_like, params),
                 "exp_avg_sq": jax.tree_util.tree_map(jnp.zeros_like,
                                                      params)}
    first = last = None
    for step in range(1, 81):
        params, opt_state, loss = step_fn(params, opt_state, scaler,
                                          jnp.int32(step), x, y)
        if step == 1:
            first = float(loss)
        if step % 20 == 0:
            print(f"step {step:3d} loss {float(loss):.4f}")
        last = float(loss)

    assert last < first * 0.1, (first, last)
    print(f"OK: loss {first:.3f} -> {last:.4f} on "
          f"{comm.num_devices()} devices (tp={comm.model_parallel_size()},"
          f" dp={comm.data_parallel_size()})")


if __name__ == "__main__":
    main()
