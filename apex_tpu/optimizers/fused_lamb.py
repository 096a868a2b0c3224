"""FusedLAMB (reference: apex/optimizers/fused_lamb.py).

LAMB = Adam moments + per-tensor trust ratio (||p||/||update||), with an
optional global-gradient-norm clip computed first — the reference's
two-stage multi_tensor_lamb with a multi_tensor_l2norm prologue
(SURVEY.md §2.1).  The global norm here is one fused reduction across the
pytree; the trust ratio stays per-leaf exactly as the reference keeps it
per-tensor.

Flat AMP pipeline: ``step()`` takes already-packed per-bucket gradient
buffers and a traced pipeline ``clip_coef`` folded into the gradient
scaling (optimizers/_base._fold_clip).  The two clips COMPOSE: the
max_grad_norm prologue divides its measured norm by the effective
grad_scale, so it judges the gradients as the pipeline already clipped
them — prefer ONE owner (pipeline ``max_grad_norm`` or LAMB's, not
both) unless double clipping is intended.
"""

from __future__ import annotations

import jax.numpy as jnp

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import _functional as F
from apex_tpu.optimizers._base import FusedOptimizerBase, tree_map, unzip_tree


class FusedLAMB(FusedOptimizerBase):
    defaults = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-6,
                    weight_decay=0.01, amsgrad=False, adam_w_mode=True,
                    grad_averaging=True, set_grad_none=True,
                    bias_correction=True, max_grad_norm=1.0,
                    use_nvlamb=False)

    # The two-phase update reads the work buffers in both phases, and
    # with them donated XLA:TPU assigns the gradient pack's relayouts
    # to memory spaces differently: +1.65 ms of copies a step at
    # BERT-Large on a v5e, where Adam's one-phase step LOSES four f32
    # bucket copies to the same donation (PERF.md section 6, PR 33).
    # Donating the state and the model-dtype parameters alone compiles
    # to the undonated program's schedule, so LAMB keeps to that.
    _donate_work = False

    def __init__(self, params, betas=None, **kw):
        if betas is not None:
            kw["beta1"], kw["beta2"] = betas
        if kw.pop("amsgrad", False):
            raise RuntimeError("FusedLAMB does not support the AMSGrad "
                               "variant.")
        super().__init__(params, **kw)

    def init_state(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"exp_avg": tree_map(zeros, params),
                "exp_avg_sq": tree_map(zeros, params)}

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        h = self._merge_hypers(hypers)
        gnorm = F.global_grad_norm(grads) / grad_scale
        maxn = h["max_grad_norm"]
        clip = jnp.where((maxn > 0) & (gnorm > maxn),
                         maxn / gnorm, jnp.float32(1.0))

        def leaf(p, g, m, v):
            return F.lamb_step(
                p, g, m, v, lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
                eps=h["eps"], weight_decay=h["weight_decay"], step=step,
                bias_correction=self.hypers["bias_correction"],
                grad_scale=grad_scale,
                clip_coeff=clip, use_nvlamb=self.hypers["use_nvlamb"])

        out = tree_map(leaf, params, grads, opt_state["exp_avg"],
                       opt_state["exp_avg_sq"])
        new_p, new_m, new_v = unzip_tree(params, out, 3)
        return new_p, {"exp_avg": new_m, "exp_avg_sq": new_v}

    def _flat_prologue(self, work_bufs, grad_bufs, step, grad_scale,
                       hypers):
        """Global-grad-norm clip coefficient, computed once across ALL
        buckets (the reference's multi_tensor_l2norm prologue): one
        fused reduction per bucket, rss-combined."""
        h = self._merge_hypers(hypers)
        gnorm = jnp.sqrt(sum(mt.flat_l2norm_ref(g) ** 2 for g in grad_bufs))
        gnorm = gnorm / grad_scale
        maxn = h["max_grad_norm"]
        return jnp.where((maxn > 0) & (gnorm > maxn),
                         maxn / gnorm, jnp.float32(1.0))

    def _flat_bucket_step(self, bucket_index, p, g, state, step, grad_scale,
                          hypers, extra, keep=None, model_dtype=None):
        h = self._merge_hypers(hypers)
        po, mo, vo, *pm = mt.flat_lamb(
            p, g, state["exp_avg"], state["exp_avg_sq"],
            self._plan.segment_sizes(bucket_index),
            lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"], eps=h["eps"],
            weight_decay=h["weight_decay"], step=step,
            bias_correction=self.hypers["bias_correction"],
            grad_scale=grad_scale, clip_coeff=extra,
            use_nvlamb=self.hypers["use_nvlamb"], keep=keep,
            model_dtype=model_dtype)
        return po, {"exp_avg": mo, "exp_avg_sq": vo}, *pm


class FusedMixedPrecisionLamb(FusedLAMB):
    """Reference: apex/optimizers/fused_mixed_precision_lamb.py — LAMB
    stepping f32 masters for low-precision model params.  The base class
    already keeps masters whenever params are bf16/fp16; this subclass
    just forces it on."""

    def __init__(self, params, **kw):
        kw["master_weights"] = True
        super().__init__(params, **kw)
