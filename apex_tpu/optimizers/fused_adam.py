"""FusedAdam (reference: apex/optimizers/fused_adam.py).

Adam/AdamW with the whole-pytree update traced into one jitted program
(XLA fuses it the way multi_tensor_adam.cu fused CUDA launches,
SURVEY.md §3.3).  ``adam_w_mode=True`` (default, as in the reference)
gives AdamW decoupled decay; ``capturable`` is accepted for parity and
ignored (every step is a compiled graph on TPU).

Flat AMP pipeline: ``step()`` accepts the bucket plan's per-bucket flat
gradient buffers (or an ``amp.FlatGrads`` bundle) plus a traced
``clip_coef`` — the clip folds into the update's own unscaling of the
gradients (``flat_adam``), so a clipped step reads the gradients
exactly once (see optimizers/_base._fold_clip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import _functional as F
from apex_tpu.optimizers._base import FusedOptimizerBase, tree_map, unzip_tree


class FusedAdam(FusedOptimizerBase):
    defaults = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                    weight_decay=0.0, adam_w_mode=True, bias_correction=True,
                    amsgrad=False, capturable=False, set_grad_none=True)

    def __init__(self, params, betas=None, **kw):
        if betas is not None:
            kw["beta1"], kw["beta2"] = betas
        if kw.pop("amsgrad", False):
            raise RuntimeError("FusedAdam does not support the AMSGrad "
                               "variant.")  # reference raises identically
        super().__init__(params, **kw)

    def init_state(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"exp_avg": tree_map(zeros, params),
                "exp_avg_sq": tree_map(zeros, params)}

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        h = self._merge_hypers(hypers)

        def leaf(p, g, m, v):
            return F.adam_step(
                p, g, m, v, lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
                eps=h["eps"], weight_decay=h["weight_decay"], step=step,
                adam_w_mode=self.hypers["adam_w_mode"],
                bias_correction=self.hypers["bias_correction"],
                grad_scale=grad_scale)

        out = tree_map(leaf, params, grads, opt_state["exp_avg"],
                       opt_state["exp_avg_sq"])
        new_p, new_m, new_v = unzip_tree(params, out, 3)
        return new_p, {"exp_avg": new_m, "exp_avg_sq": new_v}

    def _flat_bucket_step(self, bucket_index, p, g, state, step, grad_scale,
                          hypers, extra, keep=None, model_dtype=None):
        h = self._merge_hypers(hypers)
        with jax.named_scope("apex_optim/moments"):
            po, mo, vo, *pm = mt.flat_adam(
                p, g, state["exp_avg"], state["exp_avg_sq"],
                lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"], eps=h["eps"],
                weight_decay=h["weight_decay"], step=step,
                adam_w_mode=self.hypers["adam_w_mode"],
                bias_correction=self.hypers["bias_correction"],
                grad_scale=grad_scale, keep=keep, model_dtype=model_dtype)
        return po, {"exp_avg": mo, "exp_avg_sq": vo}, *pm
