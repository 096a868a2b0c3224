"""FusedNovoGrad (reference: apex/optimizers/fused_novograd.py).

NovoGrad: layer-wise (per-tensor scalar) second moment normalizing the
gradient before the first-moment EMA; cf. csrc/multi_tensor_novograd.cu.

Flat AMP pipeline: ``step()`` takes already-packed per-bucket gradient
buffers and a traced ``clip_coef`` folded into the gradient scaling
(optimizers/_base._fold_clip); the per-tensor second-moment norms are
then norms of the CLIPPED gradients, matching the per-leaf oracle fed
pre-clipped grads.
"""

from __future__ import annotations

import jax.numpy as jnp

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import _functional as F
from apex_tpu.optimizers._base import FusedOptimizerBase, tree_map, unzip_tree


class FusedNovoGrad(FusedOptimizerBase):
    defaults = dict(lr=1e-3, beta1=0.95, beta2=0.98, eps=1e-8,
                    weight_decay=0.0, grad_averaging=True, amsgrad=False,
                    bias_correction=True, reg_inside_moment=False,
                    norm_type=2, init_zero=False, set_grad_none=True)

    def __init__(self, params, betas=None, **kw):
        if betas is not None:
            kw["beta1"], kw["beta2"] = betas
        if kw.pop("amsgrad", False):
            raise RuntimeError("FusedNovoGrad does not support the AMSGrad "
                               "variant.")
        super().__init__(params, **kw)

    def init_state(self, params):
        return {
            "exp_avg": tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "exp_avg_sq": tree_map(
                lambda p: jnp.zeros((), jnp.float32), params),
        }

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        h = self._merge_hypers(hypers)
        first = step == 1

        if self.hypers["norm_type"] != 2:
            raise ValueError("FusedNovoGrad only supports norm_type=2")

        def leaf(p, g, m, v):
            return F.novograd_step(
                p, g, m, v, lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
                eps=h["eps"], weight_decay=h["weight_decay"],
                first_run=first,
                grad_averaging=self.hypers["grad_averaging"],
                grad_scale=grad_scale,
                init_zero=self.hypers["init_zero"],
                reg_inside_moment=self.hypers["reg_inside_moment"])

        out = tree_map(leaf, params, grads, opt_state["exp_avg"],
                       opt_state["exp_avg_sq"])
        new_p, new_m, new_v = unzip_tree(params, out, 3)
        return new_p, {"exp_avg": new_m, "exp_avg_sq": new_v}

    def _flat_bucket_step(self, bucket_index, p, g, state, step, grad_scale,
                          hypers, extra, keep=None, model_dtype=None):
        if self.hypers["norm_type"] != 2:
            raise ValueError("FusedNovoGrad only supports norm_type=2")
        h = self._merge_hypers(hypers)
        # per-tensor second moments ride the bucket's static segment
        # sizes: the packed exp_avg_sq is one (num leaves,) vector per
        # bucket
        po, mo, vo, *pm = mt.flat_novograd(
            p, g, state["exp_avg"], state["exp_avg_sq"],
            self._plan.segment_sizes(bucket_index),
            lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"], eps=h["eps"],
            weight_decay=h["weight_decay"], first_run=step == 1,
            grad_averaging=self.hypers["grad_averaging"],
            init_zero=self.hypers["init_zero"],
            reg_inside_moment=self.hypers["reg_inside_moment"],
            grad_scale=grad_scale, keep=keep, model_dtype=model_dtype)
        return po, {"exp_avg": mo, "exp_avg_sq": vo}, *pm
