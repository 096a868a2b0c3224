"""apex.contrib.xentropy parity (reference:
apex/contrib/xentropy/softmax_xentropy.py, SURVEY.md §2.3).

The reference's `SoftmaxCrossEntropyLoss` is a torch.autograd.Function
whose forward calls `xentropy_cuda.forward(logits, labels, smoothing,
half_to_float)` then zeroes losses at `padding_idx`; backward masks
grads the same way.  Here the fused kernel is
apex_tpu.ops.xentropy.softmax_cross_entropy (Pallas, custom_vjp), and the
padding mask is a `jnp.where` outside it — which differentiates to
exactly the reference's masked backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops.xentropy import softmax_cross_entropy


def softmax_cross_entropy_loss(logits, labels, smoothing=0.0,
                               padding_idx=0, half_to_float=False):
    """Per-example losses (N,), zeroed where labels == padding_idx."""
    losses = softmax_cross_entropy(logits, labels, smoothing, half_to_float)
    with jax.named_scope("apex_xentropy"):
        return jnp.where(labels == padding_idx,
                         jnp.zeros((), losses.dtype), losses)


class SoftmaxCrossEntropyLoss:
    """API-parity facade for the reference autograd.Function: use
    ``SoftmaxCrossEntropyLoss.apply(logits, labels, ...)`` exactly as with
    the reference; it is differentiable through jax.grad."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False):
        return softmax_cross_entropy_loss(
            logits, labels, smoothing, padding_idx, half_to_float)
