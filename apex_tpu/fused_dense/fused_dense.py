"""Epilogue-fused dense layers (reference: apex/fused_dense/fused_dense.py
+ csrc/fused_dense.cpp using cuBLASLt epilogues).

GEMM+bias and GEMM+bias+GELU+GEMM+bias: on TPU these epilogues are
exactly what XLA fuses into the matmul, so the module keeps the
reference's API while a single jit region delivers the fusion
(SURVEY.md §2.4).  f32 accumulation via preferred_element_type.

fp8 path (``fp8_matmul`` / ``fp8=Fp8Policy(...)`` on the modules and
functions): operands quantize to e4m3 in the forward and the incoming
cotangent to e5m2 in the backward — fp8-capable MXUs run these dots at
~2x the bf16 rate.  Scales follow the delayed-scaling discipline of
``apex_tpu.amp.fp8``: pass ``w_scale=`` (and ``x_scale=``/``g_scale=``)
from the packed per-bucket state for delayed scaling, or omit them for
just-in-time (current) scaling.  Exactly ONE quantize convert per
operand and ONE per cotangent — the e5m2 cotangent is shared by dx and
dw — pinned program-wide by the apexverify spec ``amp.fp8_step``.
Where the backend cannot compile fp8 dots the quantization still runs
and the dot upcasts to bf16 (the bit-identical-bookkeeping fallback;
docs/amp.md "fp8 training" fallback matrix).
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.fp8 import Fp8Policy, dynamic_scale, quantize


def _fp8_operand(q, policy: Fp8Policy):
    """The dot operand for a quantized array: fp8 straight to the MXU
    where the backend compiles it, else the bf16-compute oracle
    (upcast AFTER quantization — the rounding, saturation and scale
    bookkeeping are identical on both paths)."""
    if policy.uses_fp8_compute():
        return q
    return q.astype(jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_matmul(policy: Fp8Policy, x, w, x_scale, w_scale, g_scale):
    out, _ = _fp8_matmul_fwd(policy, x, w, x_scale, w_scale, g_scale)
    return out


def _fp8_matmul_fwd(policy, x, w, x_scale, w_scale, g_scale):
    qx = quantize(x, x_scale, policy.fwd_dtype() or policy.fwd_format)
    qw = quantize(w, w_scale, policy.fwd_dtype() or policy.fwd_format)
    acc = jax.lax.dot_general(
        _fp8_operand(qx, policy), _fp8_operand(qw, policy),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out = (acc / (jnp.asarray(x_scale, jnp.float32)
                  * jnp.asarray(w_scale, jnp.float32))).astype(x.dtype)
    # zero-size dtype carriers: residual leaves must be arrays, and the
    # backward needs the PRIMAL dtypes for its cotangent casts
    return out, (qx, qw, x_scale, w_scale, g_scale,
                 jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))


def _fp8_matmul_bwd(policy, res, g):
    qx, qw, sx, sw, sg, x_like, w_like = res
    sg_primal_none = sg is None
    if sg_primal_none:
        sg = dynamic_scale(g, policy.bwd_max())
    # ONE e5m2 quantize of the cotangent, shared by dx and dw — casts
    # must never silently multiply (spec amp.fp8_step pins the count)
    qg = quantize(g, sg, policy.bwd_dtype() or policy.bwd_format)
    og, ow, ox = (_fp8_operand(qg, policy), _fp8_operand(qw, policy),
                  _fp8_operand(qx, policy))
    sx = jnp.asarray(sx, jnp.float32)
    sw = jnp.asarray(sw, jnp.float32)
    sg = jnp.asarray(sg, jnp.float32)
    # dx = g @ w.T: contract the output dim
    dx = jax.lax.dot_general(
        og, ow, (((og.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / (sg * sw)
    # dw = x.T @ g over all leading dims
    k = ox.shape[-1]
    n = og.shape[-1]
    dw = jax.lax.dot_general(
        ox.reshape(-1, k), og.reshape(-1, n),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / (sx * sg)
    # scales are non-differentiable data: symbolic-zero cotangents
    return (dx.astype(x_like.dtype), dw.astype(w_like.dtype),
            jnp.zeros_like(sx), jnp.zeros_like(sw),
            None if sg_primal_none else jnp.zeros_like(sg))


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


@jax.named_scope("apex_linear")
def fp8_matmul(x, w, *, policy: Optional[Fp8Policy] = None,
               x_scale=None, w_scale=None, g_scale=None):
    """``(..., K) @ (K, N)`` through the fp8 path.

    Forward: x and w quantize to the policy's forward format (e4m3)
    with ``x_scale``/``w_scale`` — the DELAYED per-tensor scales from
    the packed state (``FusedOptimizerBase.fp8_scales()`` /
    ``amp.fp8.scales_tree``), or just-in-time amax scaling when
    omitted.  Backward: the cotangent quantizes ONCE to the backward
    format (e5m2) with ``g_scale`` (delayed) or current scaling, and
    feeds both dx and dw.  f32 accumulation throughout; output in
    ``x.dtype``.
    """
    if policy is None:
        policy = Fp8Policy()
    if x_scale is None:
        x_scale = dynamic_scale(x, policy.fwd_max())
    if w_scale is None:
        w_scale = dynamic_scale(w, policy.fwd_max())
    return _fp8_matmul(policy, x, w, x_scale, w_scale, g_scale)


@jax.named_scope("apex_linear")
def fused_dense_function(x, weight, bias=None, fp8=None, w_scale=None):
    """y = x @ W^T + b (torch Linear weight layout: (out, in)).

    ``fp8``: an :class:`~apex_tpu.amp.fp8.Fp8Policy` routes the matmul
    through :func:`fp8_matmul` (``w_scale``: the weight's delayed
    per-tensor scale; omitted = just-in-time scaling)."""
    if fp8 is not None:
        y = fp8_matmul(x, weight.T, policy=fp8, w_scale=w_scale)
    else:
        y = jnp.dot(x, weight.T, preferred_element_type=jnp.float32
                    ).astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def fused_dense_gelu_dense_function(x, w1, b1, w2, b2, fp8=None,
                                    w_scales=None):
    s1, s2 = w_scales if w_scales is not None else (None, None)
    h = fused_dense_function(x, w1, b1, fp8=fp8, w_scale=s1)
    h = jax.nn.gelu(h, approximate=True)
    return fused_dense_function(h, w2, b2, fp8=fp8, w_scale=s2)


class FusedDense(nn.Module):
    """Reference-shaped: FusedDense(in_features, out_features, bias).

    ``fp8``: an :class:`~apex_tpu.amp.fp8.Fp8Policy` routes the matmul
    through the e4m3/e5m2 path (just-in-time scaling at the module
    level; thread delayed per-tensor scales through
    ``fused_dense_function(w_scale=...)`` for the packed-state
    discipline)."""
    in_features: int
    out_features: int
    bias: bool = True
    param_dtype: jnp.dtype = jnp.float32
    fp8: Optional[Fp8Policy] = None

    @nn.compact
    def __call__(self, x):
        # torch Linear weight layout is (out, in): fan-in is the LAST axis
        w = self.param("weight",
                       nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
                       (self.out_features, self.in_features),
                       self.param_dtype)
        b = (self.param("bias", nn.initializers.zeros,
                        (self.out_features,), self.param_dtype)
             if self.bias else None)
        return fused_dense_function(x, w, b, fp8=self.fp8)


class FusedDenseGeluDense(nn.Module):
    """Reference-shaped: Linear+GELU+Linear in one fused region."""
    in_features: int
    intermediate_features: int
    out_features: int
    bias: bool = True
    param_dtype: jnp.dtype = jnp.float32
    fp8: Optional[Fp8Policy] = None

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.lecun_normal(in_axis=-1, out_axis=-2)
        w1 = self.param("weight1", init,
                        (self.intermediate_features, self.in_features),
                        self.param_dtype)
        b1 = (self.param("bias1", nn.initializers.zeros,
                         (self.intermediate_features,), self.param_dtype)
              if self.bias else None)
        w2 = self.param("weight2", init,
                        (self.out_features, self.intermediate_features),
                        self.param_dtype)
        b2 = (self.param("bias2", nn.initializers.zeros,
                         (self.out_features,), self.param_dtype)
              if self.bias else None)
        return fused_dense_gelu_dense_function(x, w1, b1, w2, b2,
                                               fp8=self.fp8)
