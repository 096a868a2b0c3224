"""FusedAdagrad (reference: apex/optimizers/fused_adagrad.py);
cf. csrc/multi_tensor_adagrad.cu.

Flat AMP pipeline: ``step()`` takes already-packed per-bucket gradient
buffers and a traced ``clip_coef`` folded into ``flat_adagrad``'s
own unscaling (optimizers/_base._fold_clip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import _functional as F
from apex_tpu.optimizers._base import FusedOptimizerBase, tree_map, unzip_tree


class FusedAdagrad(FusedOptimizerBase):
    defaults = dict(lr=1e-2, eps=1e-10, weight_decay=0.0,
                    adagrad_w_mode=False, set_grad_none=True)

    def init_state(self, params):
        return {"sum": tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        h = self._merge_hypers(hypers)

        def leaf(p, g, s):
            return F.adagrad_step(p, g, s, lr=h["lr"], eps=h["eps"],
                                  weight_decay=h["weight_decay"],
                                  grad_scale=grad_scale)

        out = tree_map(leaf, params, grads, opt_state["sum"])
        new_p, new_s = unzip_tree(params, out, 2)
        return new_p, {"sum": new_s}

    def _flat_bucket_step(self, bucket_index, p, g, state, step, grad_scale,
                          hypers, extra, keep=None, model_dtype=None):
        h = self._merge_hypers(hypers)
        with jax.named_scope("apex_optim/moments"):
            po, ho, *pm = mt.flat_adagrad(
                p, g, state["sum"], lr=h["lr"], eps=h["eps"],
                weight_decay=h["weight_decay"], grad_scale=grad_scale,
                keep=keep, model_dtype=model_dtype)
        return po, {"sum": ho}, *pm
