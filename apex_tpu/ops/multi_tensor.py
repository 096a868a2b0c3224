"""Fused "foreach" math over flat parameter buffers.

TPU-native replacement for the reference's ``amp_C`` extension
(upstream-expected csrc/amp_C_frontend.cpp + multi_tensor_*.cu kernels,
SURVEY.md §2.4): scale with non-finite detection, axpby, L2 norm, and the
optimizer step math (Adam/SGD/...).  The reference chunks a list of CUDA
tensors into one grid launch to amortize launch overhead; the TPU design
concatenates pytree leaves into one flat HBM buffer (see
apex_tpu.multi_tensor_apply) and sweeps it once per phase.  All math
accumulates in f32 regardless of storage dtype; non-finite detection is
an on-device i32 flag (never a host sync — the reference's host-side
overflow read is a known sync point, SURVEY.md §3.2).

Pallas kernels (ONE pallas_call whose grid walks (rows, 128)-shaped
VMEM tiles), each with a pure-jnp oracle suffixed ``_ref`` that tests
compare against and that runs when Pallas is disabled: ``flat_scale``,
``flat_axpby``, ``flat_accumulate``, ``flat_unscale_norm``,
``flat_l2norm``.  ``flat_amax_scale_update`` is ``jnp`` over the static
segments behind the same switch, with a scatter-max ``_ref`` oracle.

XLA math (``jnp``, no kernel): the optimizer updates ``flat_adam``,
``flat_sgd``, ``flat_adagrad``, ``flat_novograd``, ``flat_lamb`` and
the segment helpers.  XLA fuses an update with the overflow skip
(``keep``), the model-dtype copy of the masters (``model_dtype``) and
LAMB's per-tensor broadcast into one sweep per phase, where a kernel is
opaque to it and pays a pad, a slice, a select and a cast around itself
(measured on the chip: PERF.md section 6, PR 28).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import interpret_mode, op_enabled
from apex_tpu.telemetry import _tape

LANE = 128
SUBLANE = 8
BLOCK_ROWS = 256  # 256x128 f32 = 128 KiB per operand tile


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _as_tiles(x: jax.Array) -> Tuple[jax.Array, int]:
    """Pad a 1-D buffer with zeros and view it as (rows, 128) tiles.

    Rows are padded to a whole grid block so kernels never read
    out-of-bounds garbage (it would poison the non-finite flag).
    """
    n = x.size
    rows = _round_up(max(pl.cdiv(n, LANE), 1), BLOCK_ROWS)
    pad = rows * LANE - n
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(rows, LANE), n


def _from_tiles(x2d: jax.Array, n: int) -> jax.Array:
    return x2d.reshape(-1)[:n]


def _grid(rows: int) -> int:
    return pl.cdiv(rows, BLOCK_ROWS)


def _vec_spec():
    return pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))


def _scalar_out_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _f32(x):
    return x.astype(jnp.float32)


def _keep_and_cast(keep, model_dtype, new, old):
    """The optimizer updates' epilogue (``new``/``old``: tuples,
    parameters first).  ``keep`` (traced bool, the step's ``found_inf == 0``):
    every result is ``where(keep, new, old)``, a select XLA fuses into
    the update's own sweep — a skipped step returns its inputs to the
    bit.  ``model_dtype``: the new parameters in that dtype as one more
    result of the same sweep."""
    if keep is not None:
        new = tuple(jnp.where(keep, n, o) for n, o in zip(new, old))
    if model_dtype is not None:
        new = new + (new[0].astype(model_dtype),)
    return new


def _all_finite(x):
    """Kernel-safe finiteness reduction: Mosaic has no is_finite
    lowering, but abs+lt covers it — |nan| < inf and |inf| < inf are
    both False, so the complement flags exactly the non-finite lanes."""
    return jnp.all(jnp.abs(x) < jnp.float32(jnp.inf))


# ---------------------------------------------------------------------------
# scale (+ non-finite check)   [reference: multi_tensor_scale_kernel.cu]
# ---------------------------------------------------------------------------

def _scale_kernel(s_ref, x_ref, o_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        flag_ref[0] = 0

    x = _f32(x_ref[...])
    y = x * s_ref[0]
    o_ref[...] = y.astype(o_ref.dtype)
    bad = jnp.logical_not(_all_finite(y)).astype(jnp.int32)
    flag_ref[0] = jnp.maximum(flag_ref[0], bad)


def flat_scale(x: jax.Array, scale: jax.Array, out_dtype=None):
    """out = x * scale over a flat buffer; returns (out, found_inf i32).

    found_inf mirrors amp_C.multi_tensor_scale's overflow buffer but stays
    on device.
    """
    out_dtype = out_dtype or x.dtype
    if not op_enabled("multi_tensor"):
        return flat_scale_ref(x, scale, out_dtype)
    x2d, n = _as_tiles(x)
    scale = jnp.asarray([scale], jnp.float32).reshape(1)
    out, flag = pl.pallas_call(
        _scale_kernel,
        grid=(_grid(x2d.shape[0]),),
        in_specs=[_smem_spec(), _vec_spec()],
        out_specs=[_vec_spec(), _scalar_out_spec()],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, out_dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret_mode(),
        name="apex_multi_tensor_scale",
    )(scale, x2d)
    return _from_tiles(out, n), flag[0]


def flat_scale_ref(x, scale, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    y = _f32(x) * jnp.float32(scale)
    bad = jnp.logical_not(jnp.all(jnp.isfinite(y))).astype(jnp.int32)
    return y.astype(out_dtype), bad


# ---------------------------------------------------------------------------
# axpby (+ non-finite check)   [reference: multi_tensor_axpby_kernel.cu]
# ---------------------------------------------------------------------------

def _axpby_kernel(s_ref, x_ref, y_ref, o_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        flag_ref[0] = 0

    r = s_ref[0] * _f32(x_ref[...]) + s_ref[1] * _f32(y_ref[...])
    o_ref[...] = r.astype(o_ref.dtype)
    bad = jnp.logical_not(_all_finite(r)).astype(jnp.int32)
    flag_ref[0] = jnp.maximum(flag_ref[0], bad)


def flat_axpby(a, x: jax.Array, b, y: jax.Array, out_dtype=None):
    """out = a*x + b*y over flat buffers; returns (out, found_inf)."""
    out_dtype = out_dtype or x.dtype
    if not op_enabled("multi_tensor"):
        return flat_axpby_ref(a, x, b, y, out_dtype)
    x2d, n = _as_tiles(x)
    y2d, _ = _as_tiles(y)
    s = jnp.stack([jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)])
    out, flag = pl.pallas_call(
        _axpby_kernel,
        grid=(_grid(x2d.shape[0]),),
        in_specs=[_smem_spec(), _vec_spec(), _vec_spec()],
        out_specs=[_vec_spec(), _scalar_out_spec()],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, out_dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret_mode(),
        name="apex_multi_tensor_axpby",
    )(s, x2d, y2d)
    return _from_tiles(out, n), flag[0]


def flat_axpby_ref(a, x, b, y, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    r = jnp.float32(a) * _f32(x) + jnp.float32(b) * _f32(y)
    bad = jnp.logical_not(jnp.all(jnp.isfinite(r))).astype(jnp.int32)
    return r.astype(out_dtype), bad


# ---------------------------------------------------------------------------
# fused gradient accumulation   [reference: the grad-accum loops around
# amp.scale_loss — per-parameter p.grad += micro.grad walks; here ONE
# read-modify-write per bucket into a donated f32 accumulator]
# ---------------------------------------------------------------------------

def _accumulate_kernel(s_ref, a_ref, g_ref, o_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        flag_ref[0] = 0

    r = a_ref[...] + _f32(g_ref[...]) * s_ref[0]
    o_ref[...] = r
    # flag the RESULT: a non-finite microbatch gradient propagates into
    # the sum (inf+x=inf, inf-inf=nan, nan+x=nan), and f32 accumulator
    # overflow is caught too — the per-microbatch latch the step skip
    # needs, from the same HBM sweep as the add
    bad = jnp.logical_not(_all_finite(r)).astype(jnp.int32)
    flag_ref[0] = jnp.maximum(flag_ref[0], bad)


def flat_accumulate(acc: jax.Array, g: jax.Array, scale=1.0):
    """acc += g * scale over flat buffers in ONE read-modify-write.

    ``acc`` is the persistent f32 accumulator bucket (ALIASED to the
    output — inside a jit that donates it, the add is in place, so a
    microbatch accumulation step moves one gradient bucket through HBM
    once and never materializes a per-leaf tree).  ``g`` may be any
    float dtype (bf16 model grads accumulate in f32).  Returns
    ``(new_acc f32, found_inf i32)``; the flag covers the accumulated
    RESULT, so one bad microbatch latches through every later add.
    """
    if acc.dtype != jnp.float32:
        raise ValueError(f"accumulator must be f32, got {acc.dtype}")
    if not op_enabled("multi_tensor"):
        return flat_accumulate_ref(acc, g, scale)
    a2d, n = _as_tiles(acc)
    g2d, _ = _as_tiles(g)
    s = jnp.asarray([scale], jnp.float32).reshape(1)
    out, flag = pl.pallas_call(
        _accumulate_kernel,
        grid=(_grid(a2d.shape[0]),),
        in_specs=[_smem_spec(), _vec_spec(), _vec_spec()],
        out_specs=[_vec_spec(), _scalar_out_spec()],
        out_shape=[
            jax.ShapeDtypeStruct(a2d.shape, jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        input_output_aliases={1: 0},
        interpret=interpret_mode(),
        name="apex_multi_tensor_accumulate",
    )(s, a2d, g2d)
    return _from_tiles(out, n), flag[0]


def flat_accumulate_ref(acc, g, scale=1.0):
    if acc.dtype != jnp.float32:
        raise ValueError(f"accumulator must be f32, got {acc.dtype}")
    r = acc + _f32(g) * jnp.asarray(scale, jnp.float32)
    bad = jnp.logical_not(jnp.all(jnp.isfinite(r))).astype(jnp.int32)
    return r, bad


# ---------------------------------------------------------------------------
# fused unscale + non-finite check + squared-L2   [reference: amp+clip
# issue multi_tensor_scale and multi_tensor_l2norm back-to-back — two
# HBM sweeps; here ONE read feeds all three outputs]
# ---------------------------------------------------------------------------

def _unscale_norm_kernel(s_ref, x_ref, o_ref, acc_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0] = jnp.float32(0.0)
        flag_ref[0] = 0

    y = _f32(x_ref[...]) * s_ref[0]
    o_ref[...] = y.astype(o_ref.dtype)
    acc_ref[0] += jnp.sum(y * y)
    bad = jnp.logical_not(_all_finite(y)).astype(jnp.int32)
    flag_ref[0] = jnp.maximum(flag_ref[0], bad)


def flat_unscale_norm(x: jax.Array, inv_scale, out_dtype=None):
    """out = x * inv_scale over a flat gradient buffer, PLUS the squared
    L2 norm of the unscaled values and the non-finite flag, all from one
    HBM sweep.  Returns (out, norm_sq f32, found_inf i32).

    This is the amp gradient epilogue (unscale_grads + check_finite +
    clip_grad_norm's reduction) collapsed into a single kernel per
    bucket: the caller rss-combines the per-bucket ``norm_sq`` into the
    global norm and max-combines the flags.  The norm is accumulated in
    f32 from the PRE-rounding unscaled values (what the clip math
    wants), and zero padding contributes nothing to either reduction.
    """
    out_dtype = out_dtype or x.dtype
    if not op_enabled("multi_tensor"):
        return flat_unscale_norm_ref(x, inv_scale, out_dtype)
    x2d, n = _as_tiles(x)
    s = jnp.asarray([inv_scale], jnp.float32).reshape(1)
    out, acc, flag = pl.pallas_call(
        _unscale_norm_kernel,
        grid=(_grid(x2d.shape[0]),),
        in_specs=[_smem_spec(), _vec_spec()],
        out_specs=[_vec_spec(), _scalar_out_spec(), _scalar_out_spec()],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, out_dtype),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret_mode(),
        name="apex_multi_tensor_unscale_norm",
    )(s, x2d)
    return _from_tiles(out, n), acc[0], flag[0]


def flat_unscale_norm_ref(x, inv_scale, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    y = _f32(x) * jnp.asarray(inv_scale, jnp.float32)
    bad = jnp.logical_not(jnp.all(jnp.isfinite(y))).astype(jnp.int32)
    return y.astype(out_dtype), jnp.sum(y * y), bad


# ---------------------------------------------------------------------------
# L2 norm   [reference: multi_tensor_l2norm_kernel.cu]
# ---------------------------------------------------------------------------

def _l2norm_kernel(x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0] = jnp.float32(0.0)

    x = _f32(x_ref[...])
    acc_ref[0] += jnp.sum(x * x)


def flat_l2norm(x: jax.Array) -> jax.Array:
    """Global L2 norm of a flat buffer (f32 accumulation)."""
    if not op_enabled("multi_tensor"):
        return flat_l2norm_ref(x)
    x2d, _ = _as_tiles(x)
    acc = pl.pallas_call(
        _l2norm_kernel,
        grid=(_grid(x2d.shape[0]),),
        in_specs=[_vec_spec()],
        out_specs=_scalar_out_spec(),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=interpret_mode(),
        name="apex_multi_tensor_l2norm",
    )(x2d)
    return jnp.sqrt(acc[0])


def flat_l2norm_ref(x):
    x = _f32(x)
    return jnp.sqrt(jnp.sum(x * x))


# ---------------------------------------------------------------------------
# Adam / AdamW step   [reference: multi_tensor_adam.cu]
# ---------------------------------------------------------------------------

def flat_adam(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step,
              adam_w_mode: bool = True, bias_correction: bool = True,
              grad_scale=1.0, keep=None, model_dtype=None):
    """One Adam/AdamW step over flat buffers.

    p may be bf16 or f32; m/v must be f32.  ``step`` is the 1-based step
    count (traced scalar ok).  With ``keep`` / ``model_dtype``
    (``_keep_and_cast``) the bucketed step's whole Adam sweep.  Returns
    (p, m, v) or (p, m, v, p_model).
    """
    m_old, v_old = m, v
    step = jnp.asarray(step, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    if not adam_w_mode:
        gf = gf + wd * pf
    m = b1 * m + (1 - b1) * gf
    v = b2 * v + (1 - b2) * gf * gf
    if bias_correction:
        c1r = 1.0 / (1.0 - b1 ** step)
        c2r = 1.0 / (1.0 - b2 ** step)
    else:
        c1r = c2r = jnp.float32(1.0)
    update = (m * c1r) / (jnp.sqrt(v * c2r) + jnp.asarray(eps, jnp.float32))
    if adam_w_mode:
        update = update + wd * pf
    p_new = (pf - jnp.asarray(lr, jnp.float32) * update).astype(p.dtype)
    return _keep_and_cast(keep, model_dtype, (p_new, m, v),
                          (p, m_old, v_old))


# ---------------------------------------------------------------------------
# SGD (momentum/nesterov/wd) step   [reference: multi_tensor_sgd_kernel.cu]
# ---------------------------------------------------------------------------

def flat_sgd(p, g, momentum_buf, *, lr, momentum=0.0, dampening=0.0,
             weight_decay=0.0, nesterov=False, first_run=False,
             grad_scale=1.0, keep=None, model_dtype=None):
    """One SGD step over flat buffers; ``keep`` / ``model_dtype`` as in
    ``flat_adam``.  Returns (p, momentum_buf[, p_model]).

    ``first_run`` may be a Python bool or a traced bool scalar."""
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    gf = gf + jnp.asarray(weight_decay, jnp.float32) * pf
    mom = jnp.asarray(momentum, jnp.float32)
    if momentum != 0.0:
        # first_run may be traced: select, don't branch
        buf = jnp.where(
            jnp.asarray(first_run, jnp.bool_), gf,
            mom * momentum_buf
            + (1 - jnp.asarray(dampening, jnp.float32)) * gf)
        step_dir = gf + mom * buf if nesterov else buf
    else:
        buf = momentum_buf
        step_dir = gf
    p_new = (pf - jnp.asarray(lr, jnp.float32) * step_dir).astype(p.dtype)
    return _keep_and_cast(keep, model_dtype, (p_new, buf),
                          (p, momentum_buf))


# ---------------------------------------------------------------------------
# Adagrad step   [reference: multi_tensor_adagrad.cu]
# ---------------------------------------------------------------------------

def flat_adagrad(p, g, h, *, lr, eps, weight_decay=0.0, grad_scale=1.0,
                 keep=None, model_dtype=None):
    """One Adagrad step over flat buffers; ``keep`` / ``model_dtype`` as
    in ``flat_adam``.  Returns (p, h[, p_model]).

    h is the running sum of squared (decayed) gradients, f32.
    """
    h_old = h
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    gf = gf + jnp.asarray(weight_decay, jnp.float32) * pf
    h = h + gf * gf
    p_new = (pf - jnp.asarray(lr, jnp.float32) * gf /
             (jnp.sqrt(h) + jnp.asarray(eps, jnp.float32))).astype(p.dtype)
    return _keep_and_cast(keep, model_dtype, (p_new, h), (p, h_old))


# ---------------------------------------------------------------------------
# segmented reductions over a bucket (per-TENSOR norms inside one flat
# buffer).  A bucket's segments are its leaves: contiguous, in order, and
# their sizes are Python ints from the bucket plan
# (``BucketPlan.segment_sizes``), so every boundary is static at trace
# time and a segment is a ``lax.slice``
# ---------------------------------------------------------------------------

def _segments(x, sizes):
    """The flat buffer's segments as static slices, in f32."""
    if sum(sizes) != x.shape[0]:
        raise ValueError(f"segment sizes sum to {sum(sizes)}, the buffer "
                         f"holds {x.shape[0]} elements")
    offset = 0
    for size in sizes:
        yield _f32(jax.lax.slice(x, (offset,), (offset + size,)))
        offset += size


def flat_segment_sumsq(x, sizes):
    """Per-segment sum of squares of a flat buffer, f32 accumulation;
    shape ``(len(sizes),)``.

    One XLA reduce per segment over its own static extent — a tree
    sum, one sweep of the buffer in total."""
    return jnp.stack([jnp.sum(seg * seg) for seg in _segments(x, sizes)])


def flat_segment_absmax(x, sizes):
    """Per-segment max(|x|) of a flat buffer, f32; shape
    ``(len(sizes),)``.

    The per-TENSOR amax the fp8 delayed-scaling state needs, from the
    same static boundaries the LAMB/NovoGrad updates use.  Non-finite
    elements propagate (|nan| is nan, |inf| is inf) so the caller's
    overflow detection sees them; an empty segment reads 0."""
    return jnp.stack([jnp.max(jnp.abs(seg), initial=0.0)
                      for seg in _segments(x, sizes)])


def flat_segment_broadcast(values, sizes):
    """Per-element buffer holding each segment's scalar over its static
    extent (``values``: ``(len(sizes),)``): a concatenate of broadcasts
    the compiler builds inside the program."""
    return jnp.concatenate([jnp.full((size,), values[j], values.dtype)
                            for j, size in enumerate(sizes)])


# ---------------------------------------------------------------------------
# fused fp8 amax + delayed-scale update   [beyond-reference: the
# transformer-engine delayed-scaling recipe collapsed to ONE flat pass
# per bucket — per-tensor amax over the plan's static segments, history
# roll, scale recompute and per-tensor overflow backoff all from that
# single sweep, never a per-leaf tree_map]
# ---------------------------------------------------------------------------

def flat_amax_scale_update(buf, sizes, amax_history, scale, *, fp8_max,
                           margin: float = 0.0,
                           backoff_factor: float = 0.5,
                           max_scale: float = 2.0 ** 24,
                           min_scale: float = 2.0 ** -24,
                           update=True):
    """One bucket's fp8 delayed-scaling bookkeeping in a single flat
    pass.  ``buf``: the bucket's flat buffer (any float dtype);
    ``sizes``: its static segment sizes (``BucketPlan.segment_sizes``);
    ``amax_history``: (num_segments, H) f32, column 0 newest;
    ``scale``: (num_segments,) f32 — the CURRENT quantization scales
    (value * scale fills the fp8 range).

    Per segment (= per tensor): amax of this step's values rolls into
    the history; the new scale is ``fp8_max / (2**margin *
    max(history))`` clipped to [min_scale, max_scale].  A segment
    whose amax is NON-FINITE is an overflow: its history holds (inf
    must never poison the window) and its scale backs off by
    ``backoff_factor`` — the loss scaler's backoff discipline layered
    per bucket.  A segment with no signal yet (all-zero history)
    keeps its old scale.  ``update`` (bool, traced ok) gates the
    whole transition — False returns the inputs unchanged (the
    scale-update-interval cadence and the external step-skip both
    ride it).

    Returns ``(new_history, new_scale, found_inf i32)`` where
    found_inf flags ANY non-finite amax in the bucket.
    """
    if not op_enabled("multi_tensor"):
        return flat_amax_scale_update_ref(
            buf, sizes, amax_history, scale, fp8_max=fp8_max, margin=margin,
            backoff_factor=backoff_factor, max_scale=max_scale,
            min_scale=min_scale, update=update)
    amax = flat_segment_absmax(buf, sizes)
    return _amax_scale_math(amax, amax_history, scale, fp8_max, margin,
                            backoff_factor, max_scale, min_scale,
                            update)


def flat_amax_scale_update_ref(buf, sizes, amax_history, scale, *,
                               fp8_max, margin: float = 0.0,
                               backoff_factor: float = 0.5,
                               max_scale: float = 2.0 ** 24,
                               min_scale: float = 2.0 ** -24,
                               update=True):
    """Oracle: per-segment amax via scatter-max through an
    element->segment id vector instead of the static slices; identical
    update math (bit-exact by test)."""
    seg_ids = flat_segment_broadcast(
        jnp.arange(len(sizes), dtype=jnp.int32), sizes)
    amax = jnp.zeros((len(sizes),), jnp.float32).at[seg_ids].max(
        jnp.abs(_f32(buf)))
    return _amax_scale_math(amax, amax_history, scale, fp8_max, margin,
                            backoff_factor, max_scale, min_scale,
                            update)


def _amax_scale_math(amax, amax_history, scale, fp8_max, margin,
                     backoff_factor, max_scale, min_scale, update):
    """The ONE delayed-scaling transition (kernel and ref paths, and
    the per-leaf oracle in amp.fp8, all funnel here so the
    bookkeeping cannot drift between layouts).

    ``update`` gates the CLEAN transition (history roll + scale
    recompute: the interval cadence, external skips).  An overflowed
    segment is handled like the loss scaler handles overflow — the
    backoff applies EVEN on a gated step (overflow response must not
    wait for the cadence), while its history always holds (inf must
    never poison the window)."""
    fmax = jnp.asarray(fp8_max, jnp.float32)
    bad_seg = jnp.logical_not(jnp.abs(amax) < jnp.float32(jnp.inf))
    found_inf = jnp.any(bad_seg).astype(jnp.int32)
    safe_amax = jnp.where(bad_seg, jnp.float32(0.0), amax)
    rolled = jnp.concatenate(
        [safe_amax[:, None], amax_history[:, :-1]], axis=1)
    amax_max = jnp.max(rolled, axis=1)
    recomputed = jnp.where(
        amax_max > 0,
        jnp.clip(fmax / (jnp.float32(2.0) ** jnp.asarray(
            margin, jnp.float32) * amax_max),
            jnp.asarray(min_scale, jnp.float32),
            jnp.asarray(max_scale, jnp.float32)),
        scale)
    upd = jnp.asarray(update, jnp.bool_)
    hold = jnp.logical_or(bad_seg, jnp.logical_not(upd))
    new_hist = jnp.where(hold[:, None], amax_history, rolled)
    new_scale = jnp.where(upd, recomputed, scale)
    new_scale = jnp.where(
        bad_seg,
        jnp.maximum(scale * jnp.asarray(backoff_factor, jnp.float32),
                    jnp.asarray(min_scale, jnp.float32)),
        new_scale)
    return new_hist, new_scale, found_inf


# ---------------------------------------------------------------------------
# NovoGrad step (segmented)   [reference: multi_tensor_novograd.cu]
# ---------------------------------------------------------------------------

def flat_novograd(p, g, m, v_seg, sizes, *, lr, beta1, beta2, eps,
                  weight_decay=0.0, first_run=False, grad_averaging=True,
                  init_zero=False, reg_inside_moment=False, grad_scale=1.0,
                  keep=None, model_dtype=None):
    """One NovoGrad step over a flat bucket; ``keep`` / ``model_dtype``
    as in ``flat_adam``.  Returns (p, m, v_seg[, p_model]).

    ``v_seg`` is the per-TENSOR second moment, one f32 scalar per bucket
    segment (shape ``(len(sizes),)``); ``sizes`` are the bucket's static
    segment sizes (``BucketPlan.segment_sizes``).  The per-segment
    gradient norms are reduced over those static extents and the
    normalizer is broadcast back over them inside the elementwise sweep.
    ``first_run`` may be a Python bool or a traced bool scalar.
    """
    m_old = m
    pf = _f32(p)
    gf = _f32(g) / jnp.asarray(grad_scale, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    first = jnp.asarray(first_run, jnp.bool_)
    with jax.named_scope("apex_optim/grad_norm"):
        g_norm_sq = flat_segment_sumsq(gf, sizes)
        if init_zero:
            v_new = jnp.where(first, (1 - b2) * g_norm_sq,
                              b2 * v_seg + (1 - b2) * g_norm_sq)
        else:
            v_new = jnp.where(first, g_norm_sq,
                              b2 * v_seg + (1 - b2) * g_norm_sq)
        denom = jnp.sqrt(v_new) + jnp.asarray(eps, jnp.float32)
    with jax.named_scope("apex_optim/moments"):
        gn = gf / flat_segment_broadcast(denom, sizes)
        if reg_inside_moment:
            gn = gn + wd * pf
        coeff = (1 - b1) if grad_averaging else jnp.float32(1.0)
        m = jnp.where(first, gn, b1 * m + coeff * gn)
        update = m if reg_inside_moment else m + wd * pf
        p_new = (pf - jnp.asarray(lr, jnp.float32) * update).astype(p.dtype)
        return _keep_and_cast(keep, model_dtype, (p_new, m, v_new),
                              (p, m_old, v_seg))


# ---------------------------------------------------------------------------
# LAMB step (segmented)   [reference: multi_tensor_lamb.cu stage1+stage2]
# ---------------------------------------------------------------------------

@jax.named_scope("apex_optim/trust_ratio")
def _lamb_trust_factor(p, update, sizes, lr, wd, use_nvlamb):
    """Per-element lr*trust buffer: per-segment norms over the static
    ``sizes``, each segment's scalar broadcast back over its extent."""
    p_norm = jnp.sqrt(flat_segment_sumsq(p, sizes))
    u_norm_sq = flat_segment_sumsq(update, sizes)
    u_norm = jnp.sqrt(u_norm_sq)
    trust = jnp.where((p_norm > 0) & (u_norm > 0), p_norm / u_norm, 1.0)
    if not use_nvlamb:
        # standard LAMB exempts decay-free tensors from layer adaptation;
        # NVLAMB applies the trust ratio to every layer
        trust = jnp.where(wd == 0.0, jnp.float32(1.0), trust)
    # telemetry from the reductions that already exist — per-bucket
    # emissions combine across buckets: max for the trust ratio, root-sum-square for the
    # update norm.  No extra HBM sweep: u_norm_sq is (num_segments,).
    _tape.emit("optim/max_trust_ratio", jnp.max(trust), reduce="max")
    _tape.emit("optim/update_norm", jnp.sqrt(jnp.sum(u_norm_sq)),
               reduce="rss")
    return flat_segment_broadcast(jnp.asarray(lr, jnp.float32) * trust,
                                  sizes)


def flat_lamb(p, g, m, v, sizes, *, lr, beta1, beta2, eps,
              weight_decay=0.0, step=1, bias_correction=True,
              grad_scale=1.0, clip_coeff=1.0, use_nvlamb=False,
              keep=None, model_dtype=None):
    """One LAMB step over a flat bucket; ``keep`` / ``model_dtype`` as in
    ``flat_adam``.  Returns (p, m, v[, p_model]).

    Two phases (the reference's stage1+stage2 shape): moments + unscaled
    update, then the trust-ratio-scaled apply.  The per-TENSOR trust
    ratio ||p||/||update|| is reduced over the bucket's static segment
    ``sizes`` (``BucketPlan.segment_sizes``).  ``clip_coeff`` is the
    precomputed global-grad-norm clip factor (stage-1 side input).  The
    skip is a select, not the trust factor multiplied by zero: after an
    overflow ``update`` holds inf/nan.
    """
    m_old, v_old = m, v
    step = jnp.asarray(step, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    with jax.named_scope("apex_optim/moments"):
        pf = _f32(p)
        gf = _f32(g) * (jnp.asarray(clip_coeff, jnp.float32)
                        / jnp.asarray(grad_scale, jnp.float32))
        m = b1 * m + (1 - b1) * gf
        v = b2 * v + (1 - b2) * gf * gf
        if bias_correction:
            c1r = 1.0 / (1.0 - b1 ** step)
            c2r = 1.0 / (1.0 - b2 ** step)
        else:
            c1r = c2r = jnp.float32(1.0)
        update = (m * c1r) / (jnp.sqrt(v * c2r)
                              + jnp.asarray(eps, jnp.float32)) + wd * pf
    factor = _lamb_trust_factor(pf, update, sizes, lr, wd, use_nvlamb)
    with jax.named_scope("apex_optim/apply"):
        p_new = (pf - factor * update).astype(p.dtype)
        return _keep_and_cast(keep, model_dtype, (p_new, m, v),
                              (p, m_old, v_old))
