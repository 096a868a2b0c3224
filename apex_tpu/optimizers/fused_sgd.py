"""FusedSGD (reference: apex/optimizers/fused_sgd.py).

torch.optim.SGD semantics (momentum / dampening / nesterov / weight
decay) as one fused pytree update; cf. csrc/multi_tensor_sgd_kernel.cu.

Flat AMP pipeline: ``step()`` takes already-packed per-bucket gradient
buffers and a traced ``clip_coef`` folded into ``flat_sgd``'s own
unscaling (optimizers/_base._fold_clip) — no per-leaf clip pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import _functional as F
from apex_tpu.optimizers._base import FusedOptimizerBase, tree_map, unzip_tree


class FusedSGD(FusedOptimizerBase):
    defaults = dict(lr=1e-3, momentum=0.0, dampening=0.0, weight_decay=0.0,
                    nesterov=False, wd_after_momentum=False,
                    materialize_master_grads=True, set_grad_none=False)

    def __init__(self, params, **kw):
        if kw.get("nesterov") and (
                kw.get("momentum", 0.0) <= 0 or kw.get("dampening", 0.0) != 0):
            raise ValueError(
                "Nesterov momentum requires a momentum and zero dampening")
        super().__init__(params, **kw)

    def init_state(self, params):
        return {"momentum_buffer":
                tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        h = self._merge_hypers(hypers)
        first = step == 1

        def leaf(p, g, buf):
            return F.sgd_step(
                p, g, buf, lr=h["lr"],
                momentum=self.hypers["momentum"],
                dampening=self.hypers["dampening"],
                weight_decay=h["weight_decay"],
                nesterov=self.hypers["nesterov"],
                first_run=first, grad_scale=grad_scale)

        out = tree_map(leaf, params, grads, opt_state["momentum_buffer"])
        new_p, new_b = unzip_tree(params, out, 2)
        return new_p, {"momentum_buffer": new_b}

    def _flat_bucket_step(self, bucket_index, p, g, state, step, grad_scale,
                          hypers, extra, keep=None, model_dtype=None):
        h = self._merge_hypers(hypers)
        with jax.named_scope("apex_optim/moments"):
            po, bo, *pm = mt.flat_sgd(
                p, g, state["momentum_buffer"], lr=h["lr"],
                momentum=self.hypers["momentum"],
                dampening=self.hypers["dampening"],
                weight_decay=h["weight_decay"],
                nesterov=self.hypers["nesterov"],
                first_run=step == 1, grad_scale=grad_scale, keep=keep,
                model_dtype=model_dtype)
        return po, {"momentum_buffer": bo}, *pm
