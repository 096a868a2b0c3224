"""Fused softmax cross-entropy with label smoothing (reference:
apex/contrib/csrc/xentropy/ — `xentropy_cuda.forward/backward`,
SURVEY.md §2.3/§2.4).

The reference fuses logsumexp + target-logit gather into one kernel and
computes the backward in-place from the saved `max_log_sum_exp`.  Here the
same fusion is one Pallas row pass: forward computes, per row of logits,

    lse    = logsumexp(x)
    loss   = lse - (1-eps) * x[target] - eps * mean(x)

(the standard label-smoothing decomposition: (1-eps)*NLL + eps*uniform-KL
up to a constant, exactly the reference's formula).  The gather is done
in-register via an iota==target one-hot — no HBM gather op.  Backward
recomputes softmax from the saved per-row lse (cheaper than saving the
full probability matrix):

    dx = dy * (softmax(x) - (1-eps)*onehot - eps/C)

All math in f32 regardless of input dtype; `half_to_float` keeps the
reference's contract of emitting f32 losses/grads from half inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import interpret_mode, op_enabled

LANE = 128
_MAX_C = 65536          # beyond this, the XLA path wins anyway


def _block_rows(c: int) -> int:
    rows = max(8, min(256, (512 * 1024) // (c * 4)))
    return rows - rows % 8


def _use_pallas(c: int) -> bool:
    return op_enabled("xentropy") and c % LANE == 0 and c <= _MAX_C


def _fwd_kernel(smoothing, x_ref, t_ref, loss_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)              # (br, C)
    t = t_ref[...]                                  # (br, LANE) broadcast
    br, c = x.shape
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    lse = m + jnp.log(jnp.sum(e, axis=1, keepdims=True))
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, c), 1)
    onehot = cols == t[:, :1]
    xt = jnp.sum(jnp.where(onehot, x, 0.0), axis=1, keepdims=True)
    loss = lse - (1.0 - smoothing) * xt
    if smoothing:
        loss = loss - smoothing * jnp.mean(x, axis=1, keepdims=True)
    loss_ref[...] = jnp.broadcast_to(loss, (br, LANE))
    lse_ref[...] = jnp.broadcast_to(lse, (br, LANE))


def _bwd_kernel(smoothing, x_ref, t_ref, lse_ref, dy_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...]
    lse = lse_ref[...][:, :1]
    dy = dy_ref[...][:, :1].astype(jnp.float32)
    br, c = x.shape
    p = jnp.exp(x - lse)
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, c), 1)
    onehot = (cols == t[:, :1]).astype(jnp.float32)
    dx = p - (1.0 - smoothing) * onehot
    if smoothing:
        dx = dx - smoothing / c
    dx_ref[...] = (dy * dx).astype(dx_ref.dtype)


def _pad_rows(a, rows):
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _lane_bcast(v, rows):
    return jnp.broadcast_to(_pad_rows(v.reshape(-1, 1), rows), (rows, LANE))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_cross_entropy(logits, labels, smoothing=0.0, half_to_float=False):
    """Per-example label-smoothed cross entropy.

    logits (N, C) float, labels (N,) int.  Returns losses (N,) — f32 when
    `half_to_float` or logits are f32, else logits.dtype.  Parity:
    xentropy_cuda.forward (losses tensor; the saved max_log_sum_exp is an
    internal residual here).
    """
    return _xent_fwd(logits, labels, smoothing, half_to_float)[0]


def _loss_dtype(logits, half_to_float):
    return jnp.float32 if half_to_float else logits.dtype


@jax.named_scope("apex_xentropy")
def _xent_fwd(logits, labels, smoothing, half_to_float):
    n, c = logits.shape
    labels = labels.astype(jnp.int32)
    if not _use_pallas(c):
        xf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(xf, axis=1)
        xt = jnp.take_along_axis(xf, labels[:, None], axis=1)[:, 0]
        loss = lse - (1.0 - smoothing) * xt - smoothing * jnp.mean(xf, axis=1)
        loss = loss.astype(_loss_dtype(logits, half_to_float))
        return loss, (logits, labels, lse)
    br = _block_rows(c)
    rows = (n + br - 1) // br * br
    loss2d, lse2d = pl.pallas_call(
        functools.partial(_fwd_kernel, smoothing),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 2,
        interpret=interpret_mode(),
        name="apex_xentropy_fwd",
    )(_pad_rows(logits, rows), _lane_bcast(labels, rows).astype(jnp.int32))
    loss = loss2d[:n, 0].astype(_loss_dtype(logits, half_to_float))
    return loss, (logits, labels, lse2d[:n, 0])


@jax.named_scope("apex_xentropy")
def _xent_bwd(smoothing, half_to_float, res, dy):
    logits, labels, lse = res
    n, c = logits.shape
    out_dtype = _loss_dtype(logits, half_to_float)
    if not _use_pallas(c):
        p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
        onehot = jax.nn.one_hot(labels, c, dtype=jnp.float32)
        dx = p - (1.0 - smoothing) * onehot - smoothing / c
        dx = dy.astype(jnp.float32)[:, None] * dx
        return dx.astype(out_dtype), None
    br = _block_rows(c)
    rows = (n + br - 1) // br * br
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, smoothing),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c), out_dtype),
        interpret=interpret_mode(),
        name="apex_xentropy_bwd",
    )(_pad_rows(logits, rows),
      _lane_bcast(labels, rows).astype(jnp.int32),
      _lane_bcast(lse, rows),
      _lane_bcast(dy.astype(jnp.float32), rows))
    return dx[:n], None


softmax_cross_entropy.defvjp(_xent_fwd, _xent_bwd)


@jax.named_scope("apex_xentropy")
def softmax_cross_entropy_ref(logits, labels, smoothing=0.0,
                              half_to_float=False):
    """Pure-XLA oracle (the reference's test oracle is label-smoothed
    log_softmax NLL in stock torch)."""
    xf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(xf, axis=1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=1)[:, 0]
    smooth = -jnp.mean(logp, axis=1)
    loss = (1.0 - smoothing) * nll + smoothing * smooth
    return loss.astype(_loss_dtype(logits, half_to_float))
