"""Vocab-parallel cross entropy (reference:
apex/transformer/tensor_parallel/cross_entropy.py).

Logits arrive sharded along the vocab dim ((..., V/tp) per rank).  The
stable log-softmax needs two tiny collectives — pmax of the row max and
psum of the exp-sum — plus a psum to fetch the target logit from
whichever rank owns it.  The reference hand-writes the backward
(softmax - one_hot); here jax differentiates through the psums and
produces exactly that, so no custom_vjp is needed.  Label smoothing
matches the reference's later-era kwarg.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.transformer.tensor_parallel.mappings import (
    reduce_from_tensor_model_parallel_region as _reduce)
from apex_tpu.transformer.tensor_parallel.utils import VocabUtility

AXIS = comm.AXIS_MODEL


def _tp_bound(axis) -> bool:
    return comm.axis_is_bound(axis)


@jax.named_scope("apex_xentropy")
def vocab_parallel_cross_entropy(vocab_parallel_logits, target,
                                 label_smoothing: float = 0.0,
                                 axis: str = AXIS):
    """Per-token CE loss from vocab-sharded logits.

    vocab_parallel_logits: (..., V/tp) f32/bf16; target: (...) int ids in
    [0, V).  Returns per-token loss (...) in f32.
    """
    logits = vocab_parallel_logits.astype(jnp.float32)
    v_local = logits.shape[-1]
    synced = _tp_bound(axis)

    if synced:
        tp = comm.bound_axis_size(axis)
        rank = jax.lax.axis_index(axis)
    else:
        tp, rank = 1, 0

    # stable log-sum-exp over the GLOBAL vocab; the shift cancels in the
    # loss, so it is taken out of the grad path (pmax has no JVP rule)
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    gmax = jax.lax.pmax(local_max, axis) if synced else local_max
    # NOTE: cross-rank sums use the f/g mapping (fwd psum, bwd identity):
    # the result is consumed identically on every tp rank, so a raw psum
    # would double-count cotangents in backward (the same reason the
    # reference hand-writes these as autograd.Functions).
    shifted = logits - gmax[..., None]
    local_sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    sumexp = _reduce(local_sumexp, axis) if synced else local_sumexp
    logZ = jnp.log(sumexp)

    # target logit: owned by exactly one rank
    first, _ = VocabUtility.vocab_range_from_per_partition_vocab_size(
        v_local, rank, tp)
    local_t = target - first
    in_range = (local_t >= 0) & (local_t < v_local)
    local_t = jnp.where(in_range, local_t, 0)
    tgt_shifted = jnp.take_along_axis(
        shifted, local_t[..., None], axis=-1)[..., 0]
    tgt_shifted = jnp.where(in_range, tgt_shifted, 0.0)
    if synced:
        tgt_shifted = _reduce(tgt_shifted, axis)

    loss = logZ - tgt_shifted

    if label_smoothing > 0.0:
        # smoothed loss: (1-eps)*nll + eps/V * sum_i -log p_i
        vocab = v_local * tp
        eps = label_smoothing
        mean_logprob = jnp.sum(shifted, axis=-1)
        if synced:
            mean_logprob = _reduce(mean_logprob, axis)
        mean_logprob = mean_logprob / vocab - logZ
        loss = (1.0 - eps) * loss - eps * mean_logprob

    return loss


def cross_entropy_ref(logits, target, label_smoothing: float = 0.0):
    """Full-vocab oracle for tests."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    if label_smoothing > 0.0:
        eps = label_smoothing
        nll = (1 - eps) * nll - eps * jnp.mean(logp, axis=-1)
    return nll
