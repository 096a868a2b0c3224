"""Scaled (masked) softmax kernels (reference: csrc/megatron/
scaled_masked_softmax*.cu, scaled_upper_triang_masked_softmax*.cu,
generic_scaled_masked_softmax*, SURVEY.md §2.4).

Attention-shaped row softmax with scale and masking fused in: one VMEM
pass computes max/shift/exp/sum/normalize per row; the causal variant
builds its triangular mask from iota inside the kernel (no mask tensor in
HBM at all — the reference materializes none either).  Fully-masked rows
output ZEROS, as the reference kernel does.  Backward is the standard
softmax VJP fused the same way, consuming the saved output (zero rows
propagate zero grads automatically).

The (b, 1, sq, sk) attention mask is NOT broadcast across heads in HBM:
the kernel's BlockSpec index map routes each (head, query-block) to the
matching mask block, so the mask is read np-times from the same memory
instead of copied np-fold.

Layouts match the reference:
  scaled_masked_softmax:             x (b, np, sq, sk), mask (b, 1, sq, sk)
  scaled_upper_triang_masked_softmax: x (attn_batches, sq, sq)

Fallback to pure XLA for shapes outside the kernel's tiling envelope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import interpret_mode, op_enabled

LANE = 128
_MAX_SK = 4096          # sk*4B*block_rows must fit VMEM comfortably
_NEG = -10000.0         # reference mask fill value


def _block_rows_cap(sk: int) -> int:
    rows = max(8, min(256, (512 * 1024) // (sk * 4)))
    return rows - rows % 8


def _divisor_block(sq: int, cap: int) -> int:
    """Largest multiple of 8 that divides sq, at most cap (0 if none)."""
    br = min(cap, sq)
    br -= br % 8
    while br >= 8:
        if sq % br == 0:
            return br
        br -= 8
    return 0


def _use_pallas(sk: int) -> bool:
    return op_enabled("softmax") and sk % LANE == 0 and sk <= _MAX_SK


def _finish_rows(x):
    """Row softmax in f32 with fully-masked rows forced to zero."""
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=1, keepdims=True)
    alive = m > (_NEG / 2)
    return jnp.where(alive, e / s, 0.0)


def _masked_fwd_kernel(scale, x_ref, m_ref, y_ref):
    x = x_ref[...].astype(jnp.float32) * scale
    x = jnp.where(m_ref[...] != 0, _NEG, x)
    y_ref[...] = _finish_rows(x).astype(y_ref.dtype)


def _plain_fwd_kernel(scale, causal, sq, x_ref, y_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32) * scale
    br, sk = x.shape
    if causal:
        row_ids = (i * br + jax.lax.broadcasted_iota(
            jnp.int32, (br, sk), 0)) % sq
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (br, sk), 1)
        x = jnp.where(col_ids > row_ids, _NEG, x)
    y_ref[...] = _finish_rows(x).astype(y_ref.dtype)


def _softmax_bwd_kernel(scale, y_ref, dy_ref, dx_ref):
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    inner = jnp.sum(y * dy, axis=1, keepdims=True)
    dx_ref[...] = ((dy - inner) * y * scale).astype(dx_ref.dtype)


def _rows_call(kernel, out_dtype, x2d, br):
    """Grid over row blocks of a (rows, sk) array, no extra operands."""
    rows, sk = x2d.shape
    padded = (rows + br - 1) // br * br
    xp = jnp.pad(x2d, ((0, padded - rows), (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(padded // br,),
        in_specs=[pl.BlockSpec((br, sk), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, sk), out_dtype),
        interpret=interpret_mode(),
        name="apex_scaled_softmax",
    )(xp)
    return out[:rows]


# ---------------------------------------------------------------------------
# public ops with custom_vjp
# ---------------------------------------------------------------------------

def _check_static_scale(scale):
    """scale is a compile-time constant (custom_vjp nondiff arg, like the
    reference's Python-float attribute); jitting the raw op with scale as
    a traced argument would die deep in custom_vjp with an opaque
    UnexpectedTracerError — fail early with the fix instead."""
    if isinstance(scale, jax.core.Tracer):
        raise TypeError(
            "scale must be a static Python number (it is non-"
            "differentiable); when jitting this op directly, mark it "
            "static: jax.jit(fn, static_argnums=(<scale position>,))")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _scaled_masked_softmax_p(x, mask, scale):
    return _sms_fwd(x, mask, scale)[0]


@jax.named_scope("apex_softmax")
def scaled_masked_softmax(x, mask, scale):
    """softmax(x*scale masked_fill(mask, -10000)) over the last dim.

    x: (b, np, sq, sk); mask: (b, 1, sq, sk) with nonzero = masked, or
    None.  scale: static Python number.  Reference:
    scaled_masked_softmax_cuda.forward.
    """
    _check_static_scale(scale)
    return _scaled_masked_softmax_p(x, mask, scale)


def _sms_fwd(x, mask, scale):
    b, np_, sq, sk = x.shape
    if not _use_pallas(sk):
        y = scaled_masked_softmax_ref(x, mask, scale)
        return y, y
    if mask is None:
        kern = functools.partial(_plain_fwd_kernel, scale, False, sq)
        y = _rows_call(kern, x.dtype, x.reshape(-1, sk),
                       _block_rows_cap(sk)).reshape(x.shape)
        return y, y
    br = _divisor_block(sq, _block_rows_cap(sk))
    if br == 0:
        y = scaled_masked_softmax_ref(x, mask, scale)
        return y, y
    # mask stays (b*sq, sk); each (head, q-block) indexes its mask block
    blocks_per_head = sq // br
    m2d = mask.reshape(b * sq, sk).astype(jnp.int32)

    def mask_index(i):
        head = i // blocks_per_head        # in [0, b*np)
        b_idx = head // np_
        return (b_idx * blocks_per_head + i % blocks_per_head, 0)

    y2d = pl.pallas_call(
        functools.partial(_masked_fwd_kernel, scale),
        grid=(b * np_ * blocks_per_head,),
        in_specs=[pl.BlockSpec((br, sk), lambda i: (i, 0)),
                  pl.BlockSpec((br, sk), mask_index)],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * np_ * sq, sk), x.dtype),
        interpret=interpret_mode(),
        name="apex_scaled_masked_softmax",
    )(x.reshape(-1, sk), m2d)
    y = y2d.reshape(x.shape)
    return y, y


def _sms_bwd(scale, y, dy):
    return _softmax_vjp(y, dy, scale), None


def _softmax_vjp(y, dy, scale):
    sk = y.shape[-1]
    if not _use_pallas(sk):
        yf = y.astype(jnp.float32)
        dyf = dy.astype(jnp.float32)
        inner = jnp.sum(yf * dyf, axis=-1, keepdims=True)
        return ((dyf - inner) * yf * scale).astype(y.dtype)
    br = _block_rows_cap(sk)
    rows = y.size // sk
    padded = (rows + br - 1) // br * br
    y2 = jnp.pad(y.reshape(-1, sk), ((0, padded - rows), (0, 0)))
    dy2 = jnp.pad(dy.reshape(-1, sk), ((0, padded - rows), (0, 0)))
    dx = pl.pallas_call(
        functools.partial(_softmax_bwd_kernel, scale),
        grid=(padded // br,),
        in_specs=[pl.BlockSpec((br, sk), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, sk), y.dtype),
        interpret=interpret_mode(),
        name="apex_scaled_softmax_bwd",
    )(y2, dy2)
    return dx[:rows].reshape(y.shape)


_scaled_masked_softmax_p.defvjp(_sms_fwd, _sms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scaled_upper_triang_masked_softmax_p(x, scale):
    return _suts_fwd(x, scale)[0]


@jax.named_scope("apex_softmax")
def scaled_upper_triang_masked_softmax(x, scale):
    """Causal softmax(x*scale) for (attn_batches, sq, sq) inputs.
    scale: static Python number.  Reference:
    scaled_upper_triang_masked_softmax_cuda.forward."""
    _check_static_scale(scale)
    return _scaled_upper_triang_masked_softmax_p(x, scale)


def _suts_fwd(x, scale):
    ab, sq, sk = x.shape
    assert sq == sk, "upper-triang variant requires square attention"
    br = _divisor_block(sq, _block_rows_cap(sk))
    if _use_pallas(sk) and br:
        kern = functools.partial(_plain_fwd_kernel, scale, True, sq)
        y = _rows_call(kern, x.dtype, x.reshape(-1, sk), br
                       ).reshape(x.shape)
    else:
        y = scaled_upper_triang_masked_softmax_ref(x, scale)
    return y, y


def _suts_bwd(scale, y, dy):
    # masked entries have y == 0, so dx is already zero there
    return (_softmax_vjp(y, dy, scale),)


_scaled_upper_triang_masked_softmax_p.defvjp(_suts_fwd, _suts_bwd)


# ---------------------------------------------------------------------------
# XLA oracles / fallbacks (same fully-masked-row semantics)
# ---------------------------------------------------------------------------

def _finish_rows_ref(xf):
    m = jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.where(m > (_NEG / 2), e / s, 0.0)


def scaled_masked_softmax_ref(x, mask, scale):
    xf = x.astype(jnp.float32) * scale
    if mask is not None:
        xf = jnp.where(mask != 0, _NEG, xf)
    return _finish_rows_ref(xf).astype(x.dtype)


def scaled_upper_triang_masked_softmax_ref(x, scale):
    sq = x.shape[-1]
    causal = jnp.tril(jnp.ones((sq, sq), bool))
    xf = jnp.where(causal, x.astype(jnp.float32) * scale, _NEG)
    return _finish_rows_ref(xf).astype(x.dtype)


def generic_scaled_masked_softmax(x, mask, scale):
    """Reference generic variant (any sk): the XLA path IS the generic
    kernel here."""
    return scaled_masked_softmax(x, mask, scale)
