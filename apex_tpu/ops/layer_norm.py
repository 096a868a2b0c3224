"""Fused LayerNorm / RMSNorm forward+backward Pallas kernels.

TPU-native replacement for the reference's ``fused_layer_norm_cuda``
extension (csrc/layer_norm_cuda.cpp + layer_norm_cuda_kernel.cu,
SURVEY.md §2.4) and the contrib ``fast_layer_norm`` ext.  Row-tiled
kernels, f32 accumulation regardless of storage dtype (bf16 x f32-param
"mixed" variants fall out for free), wired into autodiff via
``jax.custom_vjp``.

Design notes (vs the CUDA original):
  - The backward RECOMPUTES mean/rstd from the saved input instead of
    plumbing per-row statistics through HBM — on TPU the op is
    HBM-bandwidth-bound, so dropping two (rows,) side arrays is a win and
    subsumes the reference's ``memory_efficient`` flag.
  - dgamma/dbeta accumulate across the sequential TPU grid into one
    (1, H) f32 block (the reference needs a two-stage cross-CTA
    reduction).
  - Hidden sizes not divisible by 128 (VPU lane width) fall back to the
    pure-XLA path, which XLA fuses well; the Pallas fast path covers the
    transformer-shaped cases, like the reference's fast_layer_norm covers
    hidden <= ~8k.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import interpret_mode, op_enabled

LANE = 128
_VMEM_BUDGET = 1024 * 1024  # per-operand block budget (bytes, f32)


def _block_rows(h: int) -> int:
    rows = max(8, min(512, _VMEM_BUDGET // (h * 4)))
    return rows - rows % 8 if rows >= 8 else 8


def _pad_rows(x2d: jax.Array, br: int) -> jax.Array:
    r = x2d.shape[0]
    pad = (-r) % br
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d


def _use_pallas(h: int) -> bool:
    # 8 is the minimum block-row count: even at the floor, one block must
    # fit the per-operand budget (the backward holds ~6 operand blocks)
    return op_enabled("layer_norm") and h % LANE == 0 and 8 * h * 4 <= _VMEM_BUDGET


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(rms, eps, x_ref, w_ref, b_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        ms = jnp.mean(x * x, axis=1, keepdims=True)
        xhat = x * jax.lax.rsqrt(ms + eps)
    else:
        mu = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mu
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(rms, eps, x_ref, w_ref, dy_ref, dx_ref, dw_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if rms:
        ms = jnp.mean(x * x, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(ms + eps)
        xhat = x * rstd
        dyw = dy * w
        m2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
        dx = (dyw - xhat * m2) * rstd
    else:
        mu = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mu
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = xc * rstd
        dyw = dy * w
        m1 = jnp.mean(dyw, axis=1, keepdims=True)
        m2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
        dx = (dyw - m1 - xhat * m2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dw_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _row_spec(br, h):
    return pl.BlockSpec((br, h), lambda i: (i, 0))


def _param_spec(h):
    return pl.BlockSpec((1, h), lambda i: (0, 0))


def _fwd_2d(x2d, w, b, eps, rms):
    r, h = x2d.shape
    br = _block_rows(h)
    xp = _pad_rows(x2d, br)
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, rms, eps),
        grid=(xp.shape[0] // br,),
        in_specs=[_row_spec(br, h), _param_spec(h), _param_spec(h)],
        out_specs=_row_spec(br, h),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2d.dtype),
        interpret=interpret_mode(),
        name="apex_fused_layer_norm_fwd" if not rms else
             "apex_fused_rms_norm_fwd",
    )(xp, w.reshape(1, h), b.reshape(1, h))
    return y[:r]


def _bwd_2d(x2d, w, dy2d, eps, rms):
    r, h = x2d.shape
    br = _block_rows(h)
    xp = _pad_rows(x2d, br)
    dyp = _pad_rows(dy2d, br)
    dx, dw, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, rms, eps),
        grid=(xp.shape[0] // br,),
        in_specs=[_row_spec(br, h), _param_spec(h), _row_spec(br, h)],
        out_specs=[_row_spec(br, h), _param_spec(h), _param_spec(h)],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="apex_fused_layer_norm_bwd" if not rms else
             "apex_fused_rms_norm_bwd",
    )(xp, w.reshape(1, h), dyp)
    return dx[:r], dw.reshape(h), db.reshape(h)


# ---------------------------------------------------------------------------
# XLA fallback (also the test oracle)
# ---------------------------------------------------------------------------

def layer_norm_ref(x, weight=None, bias=None, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_ref(x, weight=None, eps=1e-5):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wiring  (replaces the reference's autograd.Function classes,
# apex/normalization/fused_layer_norm.py::FusedLayerNormAffineFunction)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_affine(x, w, b, eps, rms):
    return _norm_affine_fwd(x, w, b, eps, rms)[0]


def _norm_affine_fwd(x, w, b, eps, rms):
    h = x.shape[-1]
    x2d = x.reshape(-1, h)
    if _use_pallas(h):
        y = _fwd_2d(x2d, w, b, eps, rms).reshape(x.shape)
    else:
        y = (rms_norm_ref(x, w, eps) if rms
             else layer_norm_ref(x, w, b, eps))
    return y, (x, w, b)


def _norm_affine_bwd(eps, rms, res, dy):
    x, w, b = res
    h = x.shape[-1]
    if _use_pallas(h):
        dx2d, dw, db = _bwd_2d(x.reshape(-1, h), w,
                               dy.reshape(-1, h), eps, rms)
        dx = dx2d.reshape(x.shape)
        dw = dw.astype(w.dtype)
        db = db.astype(b.dtype)
    else:
        def f(x, w, b):
            return (rms_norm_ref(x, w, eps) if rms
                    else layer_norm_ref(x, w, b, eps))
        _, vjp = jax.vjp(f, x, w, b)
        dx, dw, db = vjp(dy)
    if rms:
        db = jnp.zeros_like(b)
    return dx, dw, db


_norm_affine.defvjp(_norm_affine_fwd, _norm_affine_bwd)


@jax.named_scope("apex_layernorm")
def fused_layer_norm(x, weight: Optional[jax.Array] = None,
                     bias: Optional[jax.Array] = None, eps: float = 1e-5,
                     memory_efficient: bool = True):
    """LayerNorm over the last dim (reference fused_layer_norm_cuda fwd).

    ``memory_efficient`` is accepted for API parity; the TPU kernel is
    always memory-efficient (stats recomputed in backward).
    """
    del memory_efficient
    h = x.shape[-1]
    w = weight if weight is not None else jnp.ones((h,), jnp.float32)
    b = bias if bias is not None else jnp.zeros((h,), jnp.float32)
    y = _norm_affine(x, w, b, float(eps), False)
    return y


@jax.named_scope("apex_layernorm")
def fused_rms_norm(x, weight: Optional[jax.Array] = None, eps: float = 1e-5,
                   memory_efficient: bool = True):
    """RMSNorm over the last dim (reference fused_layer_norm_cuda RMS fwd)."""
    del memory_efficient
    h = x.shape[-1]
    w = weight if weight is not None else jnp.ones((h,), jnp.float32)
    b = jnp.zeros((h,), jnp.float32)
    return _norm_affine(x, w, b, float(eps), True)
