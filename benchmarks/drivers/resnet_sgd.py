"""Driver: ResNet image classification, amp O2 + FusedSGD, re-composed
from ``examples/imagenet/main_amp.py`` (its ``loss_fn`` and
``train_step`` are closures of ``main()`` there): float32 host batches
through ``apex_tpu.data.DevicePrefetcher(depth=2)``, the input cast
inside the step, ``ddp`` and ``sync_bn`` as the traffic file says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp, comm
from apex_tpu.data import DevicePrefetcher
from apex_tpu.models.resnet import Bottleneck, ResNet
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import DistributedDataParallel

from benchmarks import counts, weights
from benchmarks.jobs import AmpTrainJob

POOL = 4


def host_pool(seed, batch, image, classes):
    """POOL seeded host batches that all differ (float32 normals and
    int32 labels, as the example's)."""
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, image, image, 3), dtype=np.float32),
             rng.integers(0, classes, (batch,)).astype(np.int32))
            for _ in range(POOL)]


def _sync_bn_tree(tree):
    """The reference's tree under SyncBatchNorm's names."""
    if not isinstance(tree, dict):
        return tree
    return {("Sync" + k if k.startswith("BatchNorm_") else k):
            ({"weight": v["scale"], "bias": v["bias"]}
             if k.startswith("BatchNorm_") else _sync_bn_tree(v))
            for k, v in tree.items()}


def _plain_bn_name(name: str) -> str:
    if "SyncBatchNorm_" not in name:
        return name
    return name.replace("SyncBatchNorm_", "BatchNorm_").replace(
        "/weight", "/scale")


class Job(AmpTrainJob):
    programs = {"fwd_bwd": "train_step", "optimizer": "_full_step_flat"}
    first_update_field = "momentum_buffer"
    first_update_scale = 1.0

    def __init__(self, *, root, sizes, optimizer, traffic, reference, seed,
                 devices):
        self.seed, self.sizes = seed, sizes
        self.batch, image = traffic["batch"], sizes["image_size"]
        chips = traffic["chips"]
        use_ddp, sync_bn = traffic.get("ddp", False), traffic.get(
            "sync_bn", False)
        self.spec = reference.param_spec(sizes)
        # no dtype=: the example builds the model at its float32 default
        # and leaves precision to amp (bf16 parameters and input), so
        # the convolutions run on float32 activations
        kwargs = dict(num_classes=sizes["num_classes"], width=sizes["width"])
        if sync_bn:
            from apex_tpu.parallel import SyncBatchNorm
            kwargs["norm_cls"] = functools.partial(
                SyncBatchNorm, channel_last=True,
                process_group=comm.AXIS_DATA)
            self.to_program = _sync_bn_tree
            self.reference_name = _plain_bn_name
        model = ResNet(sizes["stage_sizes"], Bottleneck, **kwargs)
        self._mesh_made = False
        if use_ddp:
            comm.initialize(data=chips, pipe=1, ctx=1, model=1,
                            devices=list(devices)[:chips])
            self._mesh_made = True
        # running statistics start at (0, 1), the parameters come from
        # the seed: one jitted call makes both
        stats = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, image, image, 3), jnp.float32),
            train=False))["batch_stats"]
        self.batch_stats = jax.tree_util.tree_map_with_path(
            lambda path, s: (jnp.ones if path[-1].key.endswith("var")
                             else jnp.zeros)(s.shape, s.dtype), stats)
        params = self.to_program(weights.make(self.spec, seed))
        params, self.amp_state = amp.initialize(params, opt_level="O2")
        self.opt = FusedSGD(
            params, lr=optimizer["lr"], momentum=optimizer["momentum"],
            weight_decay=optimizer["weight_decay"], master_weights=True,
            masters=self.amp_state.master_params)
        del params
        ddp = DistributedDataParallel() if use_ddp else None

        def loss_fn(p, bs, x, y):
            out, updates = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            logits = out.astype(jnp.float32)
            ll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                      y[:, None], axis=1)
            return jnp.mean(ll), updates["batch_stats"]

        wrapped = self.amp_state.wrap_forward(loss_fn, cast_argnums=(2,))

        def train_step(p, bs, scaler, x, y):
            (loss, new_bs), grads, found_inf = amp.scaled_value_and_grad(
                wrapped, scaler, p, bs, x, y, has_aux=True)
            if ddp is not None:
                grads = ddp.reduce_gradients(grads)
            return loss, grads, new_bs, found_inf

        sharding = comm.sharding("data") if use_ddp else None
        self.jstep = (jax.jit(train_step, in_shardings=(
            None, None, None, sharding, sharding)) if use_ddp
            else jax.jit(train_step))

        self.pool = host_pool(seed, self.batch, image, sizes["num_classes"])

        def forever():
            i = 0
            while True:
                yield self.pool[i % POOL]
                i += 1

        self.prefetcher = DevicePrefetcher(forever(), depth=2,
                                           sharding=sharding)
        n_params = sum(int(jnp.size(x)) for x in
                       jax.tree_util.tree_leaves(self.opt.params))
        self.units_per_step = float(self.batch)
        self.counts = {
            "step_flops": counts.resnet_step_flops(
                self.batch, image=image, width=sizes["width"],
                stage_sizes=tuple(sizes["stage_sizes"]),
                classes=sizes["num_classes"]),
            "optimizer_bytes": counts.optimizer_bytes("sgd_momentum",
                                                      n_params),
            "n_params": n_params,
        }
        self._finish_init()

    def next_batch(self, i):
        return next(self.prefetcher)

    def forward_backward(self, batch):
        self._last_args = (self.opt.params, self.batch_stats,
                           self.amp_state.scaler, *batch)
        loss, grads, self.batch_stats, found_inf = self.jstep(
            *self._last_args)
        return loss, grads, found_inf

    def reference_batches(self, n):
        return [self.pool[i % POOL] for i in range(n)]

    def compiled_programs(self):
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self._last_args)
        grads = jax.eval_shape(self.jstep, *shapes)[1]
        return {
            "fwd_bwd": self.jstep.lower(*shapes).compile(),
            "optimizer": self.opt._jit_step.lower(
                *self.opt._step_args(grads, 1.0, jnp.int32(0))).compile(),
        }

    def close(self):
        self.prefetcher.close()
        if self._mesh_made:
            comm.destroy()
            self._mesh_made = False
        self.pool = self.jstep = self.batch_stats = self._last_args = None
        super().close()
