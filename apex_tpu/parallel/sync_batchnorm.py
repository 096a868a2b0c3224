"""SyncBatchNorm (reference: apex/parallel/optimized_sync_batchnorm.py +
sync_batchnorm_kernel.py, call stack SURVEY.md §3.6).

Reference structure: local Welford stats → all_gather(mean, var, count)
→ Welford combine → normalize; backward all-reduces (sum_dy, sum_dy_xmu).
TPU rebuild keeps exactly that dataflow: local stats from the Pallas
Welford kernel (apex_tpu.ops.welford), the cross-device combine is a
``psum`` of (count, sum, sumsq-equivalents) over the "data" mesh axis
inside shard_map, and the backward's reductions fall out of autodiff-ing
the psum (jax differentiates collectives), so no hand-written backward
kernel is needed.

Outside shard_map (single device or GSPMD auto-partitioning) the sync
degenerates to plain BatchNorm, matching the reference's behavior in a
single-process run.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.ops.welford import welford_mean_var_ref


def _axis_bound(axis_name: str) -> bool:
    return comm.axis_is_bound(axis_name)


@jax.named_scope("apex_syncbn")
def sync_batch_norm_stats(x2d: jax.Array, axis_name: Optional[str]):
    """Global (mean, biased var) of an (N, C) array, synced over
    ``axis_name`` when bound.

    Local stats come from the (differentiable) Welford reference path;
    the cross-device merge is Chan's combine expressed with two psums —
    numerically stable where a sum/sumsq merge would cancel
    catastrophically for large-mean activations.
    """
    mean_l, var_l, n_l = welford_mean_var_ref(x2d)
    m2_l = var_l * n_l
    if axis_name is not None and _axis_bound(axis_name):
        n, nmean = jax.lax.psum((n_l, n_l * mean_l), axis_name)
        mean = nmean / n
        # Chan: M2 = sum_i (M2_i + n_i * (mean_i - mean)^2)
        m2 = jax.lax.psum(m2_l + n_l * (mean_l - mean) ** 2, axis_name)
    else:
        n, mean, m2 = n_l, mean_l, m2_l
    var = m2 / n
    return mean, jnp.maximum(var, 0.0), n


class SyncBatchNorm(nn.Module):
    """Reference-shaped constructor (num_features, eps, momentum, affine,
    track_running_stats, channel_last); process_group is a mesh-axis name
    instead of a torch process group."""

    num_features: Optional[int] = None   # None: infer from the input
    eps: float = 1e-5
    momentum: float = 0.1
    affine: bool = True
    track_running_stats: bool = True
    process_group: Optional[str] = comm.AXIS_DATA
    channel_last: bool = False
    use_running_average: Optional[bool] = None

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_ra = nn.merge_param(
            "use_running_average", self.use_running_average,
            use_running_average)
        if self.num_features is not None:
            c = self.num_features
        else:
            c = (x.shape[-1] if self.channel_last or x.ndim == 2
                 else x.shape[1])
        if self.channel_last or x.ndim == 2:
            xc = x.reshape(-1, c)                      # (..., C)
            def restore(y2d):
                return y2d.reshape(x.shape)
        else:
            # NCHW-style: channel axis 1 (reference default layout)
            perm = (0,) + tuple(range(2, x.ndim)) + (1,)
            xt = jnp.transpose(x, perm)
            xc = xt.reshape(-1, c)
            inv = tuple(int(i) for i in jnp.argsort(jnp.array(perm)))
            def restore(y2d):
                return jnp.transpose(y2d.reshape(xt.shape), inv)

        ra_mean = self.variable("batch_stats", "running_mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "running_var",
                               lambda: jnp.ones((c,), jnp.float32))

        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean, var, n = sync_batch_norm_stats(xc, self.process_group)
            if self.track_running_stats and not self.is_initializing():
                m = self.momentum
                # torch stores UNBIASED running var
                unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
                ra_mean.value = (1 - m) * ra_mean.value + m * mean
                ra_var.value = (1 - m) * ra_var.value + m * unbiased

        if self.affine:
            w = self.param("weight", nn.initializers.ones, (c,), jnp.float32)
            b = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        with jax.named_scope("apex_syncbn"):
            y = ((xc.astype(jnp.float32) - mean)
                 * jax.lax.rsqrt(var + self.eps))
            if self.affine:
                y = y * w + b
            return restore(y.astype(x.dtype))


def convert_syncbn_model(module: Any, process_group: Optional[str] =
                         comm.AXIS_DATA, channel_last: bool = False):
    """Reference parity: apex.parallel.convert_syncbn_model recursively
    swaps torch BatchNorm modules for SyncBatchNorm.  flax modules are
    immutable dataclasses, so the equivalent is a clone with every
    nn.BatchNorm leaf replaced; models built from apex_tpu.models take a
    ``norm_cls`` factory instead — pass ``SyncBatchNorm`` there.  For a
    bare nn.BatchNorm this returns the configured SyncBatchNorm."""
    if isinstance(module, nn.BatchNorm):
        return SyncBatchNorm(
            num_features=None,               # inferred at first call
            momentum=1.0 - module.momentum,  # flax momentum is decay
            eps=module.epsilon,
            process_group=process_group,
            channel_last=channel_last,
        )
    if hasattr(module, "replace_norm"):
        return module.replace_norm(SyncBatchNorm)
    raise TypeError(
        "convert_syncbn_model supports flax nn.BatchNorm instances or "
        "modules exposing replace_norm(); build apex_tpu models with "
        "norm_cls=SyncBatchNorm instead.")
