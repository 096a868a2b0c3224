"""Fused attention kernels (reference: apex/contrib/csrc/multihead_attn/*
~8k LoC of per-variant CUDA, apex/contrib/csrc/fmha/ — SURVEY.md §2.4).

One Pallas flash-attention kernel family with flags replaces the
reference's eight hand-specialized attention extensions.  The kernel is
K-tiled with online softmax (flash-2 style: unnormalized accumulator,
one divide at the last KV block), so sequence length is bounded by HBM,
not VMEM — the (Sq, Sk) score matrix never exists, at any length.
bf16 inputs hit the MXU in bf16 and accumulate in f32.

Backward is two Pallas kernels (dq over the KV grid; dk/dv over the Q
grid) recomputing probabilities from the forward's saved logsumexp —
no probability tensor is ever stored, matching the memory behavior the
reference gets from its fused in-place bwd kernels.

Variant flags: ``causal`` prunes the iteration space (the grid's
sequential axis enumerates only the blocks on or under the diagonal —
``causal_block_plan`` — so a fully-masked block costs neither a DMA nor
a grid step); ``segment_ids``
(q-ids, kv-ids) masks cross-segment pairs, which is how contrib.fmha's
packed variable-length batches route through this one kernel.

Long-context path: ``ring_attention`` shards the KV sequence over the
"ctx" mesh axis and rotates KV blocks with lax.ppermute, merging partial
softmax statistics online — apex has NO equivalent (SURVEY.md §2.5 marks
context parallelism out of reference scope); this is the TPU-native
extension that makes long sequences first-class.

Shapes: (B, H, S, D) throughout ("bhsd").
"""

from __future__ import annotations

import functools
import itertools
import math
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import comm
from apex_tpu.ops import _dispatch
from apex_tpu.ops._dispatch import interpret_mode, op_enabled

_CompilerParams = pltpu.CompilerParams

_NEG = -1e30
_LANES = 128


def _default_scale(d: int) -> float:
    return 1.0 / math.sqrt(d)


def matmul_precision(dtype):
    """The precision contract (docs/kernels.md): f32 operands dot at
    HIGHEST (true-f32 MXU passes — default would round through bf16);
    bf16 operands keep the full-rate default.  Shared by the kernels
    and every oracle/fallback path so comparisons are apples-to-apples."""
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype) == jnp.float32 else None)


def _dot(a, b, dims):
    """Kernel dot under the precision contract, f32 accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=matmul_precision(a.dtype))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block(s: int, cap: int, explicit: bool = False) -> int:
    """Block size for a sequence dim: the largest of 1024 / 512 / 256 /
    128 that is <= cap and divides the padded length.  The cap is
    clamped to the padded length first (a short sequence runs as one
    block, whatever its length), so a cap the length is a multiple of
    is the block.  A length 1024 does not divide (1536, 2560: GPT-style
    and packed batches) steps down to 512, not to 128; an explicit
    APEX_TPU_ATTN_BLOCK_CAP that cannot be honored says so loudly,
    since the operator asked for another tile."""
    sp = _round_up(s, _LANES)
    if sp:                 # sp==0 (degenerate dim): keep old behavior
        cap = min(cap, sp)
    if sp % cap == 0:
        return cap
    blk = next(b for b in (1024, 512, 256, _LANES)
               if b <= cap and sp % b == 0)
    if explicit:
        import warnings
        warnings.warn(
            f"APEX_TPU_ATTN_BLOCK_CAP={cap} does not divide the padded "
            f"sequence length {sp}; falling back to {blk}-blocks for "
            f"this shape")
    return blk


def _attn_family(dtype) -> str:
    """Dispatch family for the flash kernel, split by precision class:
    f32 operands dot at Precision.HIGHEST (multi-pass MXU), a very
    different cost model from native-rate bf16 — so a hardware
    measurement that flips one class to the XLA path must not take the
    other down with it (kernel_bench rows map f32 shapes to
    'attention_f32')."""
    return ("attention_f32"
            if jnp.dtype(dtype) == jnp.dtype(jnp.float32) else
            "attention")


def _block_cap(dp: int, itemsize: int, s: int):
    """(cap, explicit) for a sequence dim of length ``s``: tunable via
    APEX_TPU_ATTN_BLOCK_CAP (a 128-multiple; tools/kernel_bench.py
    --sweep-attn sweeps it on hardware), else the measured-best cap the
    sweep recorded in dispatch_prefs.json for this padded head dim, else
    the default ``_default_block_cap`` reads off the call.  The env var
    is read and interpreted HERE only; ``explicit`` tells _block to
    complain loudly when the requested cap can't be honored (the
    measured table is advisory — a non-dividing measured cap quietly
    steps down to the largest block that divides)."""
    env = os.environ.get("APEX_TPU_ATTN_BLOCK_CAP")
    if env:
        try:
            cap = int(env)
        except ValueError:
            cap = -1
        if cap <= 0 or cap % _LANES:
            raise ValueError(
                f"APEX_TPU_ATTN_BLOCK_CAP must be a positive multiple "
                f"of {_LANES}, got {env!r}")
        return cap, True
    measured = _dispatch.attn_block_cap(dp)
    if measured is not None:
        # VMEM-feasibility ceiling: the measured table is advisory and
        # sweep-written (tools/kernel_bench.py only records caps that
        # compiled and won), but a hand-edited value must not push the
        # double-buffered blocks + f32 score tile past the kernels'
        # VMEM limit — clamp to the largest cap the sweep grid explores
        # for this dp.
        return min(measured, _sweep_cap_ceiling(dp)), False
    return _default_block_cap(dp, itemsize, s), False


def _default_block_cap(dp: int, itemsize: int, s: int) -> int:
    """The default sequence-block cap, from what the call shows: padded
    head dim, operand width, sequence length.  Measured on the v5e
    (PERF.md section 6, PR 32; kernels alone and inside the looped and
    the sparse-attention cells' steps): at dp 128 with 16-bit operands a
    1024 tile beats 512 wherever the sequence holds more than one of
    them — the forward's two lane reductions and dkv's transposed-LHS
    matmuls are paid per block step, a quarter as often per pair.  What
    the chip has not measured keeps what it had: a sequence of at most
    1024 (one tile would be the whole call) and float32 operands (they
    dot at Precision.HIGHEST, and at 1024 dq does not fit the scoped
    VMEM on the chip) 512; dp 256 256; wider 128 — the cap shrinks as
    the head dim grows so that the q/k/v/do blocks, double-buffered,
    stay beside the f32 score tile."""
    if dp <= _LANES:
        long_16bit = itemsize == 2 and _round_up(s, _LANES) > 1024
        return 1024 if long_16bit else 512
    return 256 if dp <= 256 else 128


def _sweep_cap_ceiling(dp: int) -> int:
    """Largest sequence-block cap the hardware sweep explores (and thus
    the largest a measured table entry can honestly contain) for a
    padded head dim — the VMEM working set grows with cap*dp."""
    return 1024 if dp <= 128 else (512 if dp <= 256 else 256)


def _geom(q, k):
    """Shared fwd/bwd tiling geometry — the saved lse layout depends on
    it, so both passes MUST derive it from this one place.  bq follows
    from (head dim, operand width, sq), bk from (head dim, operand
    width, sk): ``_block_cap`` then ``_block``, 1024-tiles for long
    16-bit sequences at dp 128 and 512 / 256 / 128 otherwise.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    dp = _round_up(d, _LANES)
    itemsize = jnp.dtype(q.dtype).itemsize
    bq = _block(sq, *_block_cap(dp, itemsize, sq))
    bk = _block(sk, *_block_cap(dp, itemsize, sk))
    sqp, skp = _round_up(sq, bq), _round_up(sk, bk)
    return b, h, sq, sk, d, dp, bq, bk, sqp, skp


def _pad_seq(x, sp):
    s = x.shape[2]
    if s == sp:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, sp - s), (0, 0)))


def _pad_head(x, dp):
    d = x.shape[3]
    if d == dp:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))


def _seg_inputs(segment_ids, b, sqp, skp):
    """Lane/sublane-broadcast segment ids so the kernel never transposes:
    q ids ride the sublanes as (B, SQP, 128); kv ids ride the lanes as
    (B, 8, SKP)."""
    q_ids, kv_ids = segment_ids
    q_ids = jnp.pad(q_ids.astype(jnp.int32),
                    ((0, 0), (0, sqp - q_ids.shape[1])),
                    constant_values=-1)
    kv_ids = jnp.pad(kv_ids.astype(jnp.int32),
                     ((0, 0), (0, skp - kv_ids.shape[1])),
                     constant_values=-2)
    qs = jnp.broadcast_to(q_ids[:, :, None], (b, sqp, _LANES))
    ks = jnp.broadcast_to(kv_ids[:, None, :], (b, 8, skp))
    return qs, ks


def _mask_for_block(j, kk, bq, bk, sq, sk, sqp, skp, causal,
                    qs_tile, ks_row, sel_tile=None, *, mask_rows):
    """Validity mask (BQ, BK) for one score block, or None if nothing
    masks.  qs_tile: (BQ, 128) or None; ks_row: (1, BK) or None;
    sel_tile: (BQ, BK) int8 of the per-query key selection (non-zero =
    the query attends the key) or None."""
    ok = None

    def _and(a, b):
        return b if a is None else a & b

    row_g = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col_g = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if skp != sk:
        ok = _and(ok, col_g < sk)
    if mask_rows and sqp != sq:
        ok = _and(ok, row_g < sq)
    if causal:
        ok = _and(ok, col_g <= row_g)
    if qs_tile is not None:
        reps = bk // _LANES
        qseg = jnp.tile(qs_tile, (1, reps)) if reps > 1 else qs_tile
        ok = _and(ok, qseg[:, :bk] == ks_row)
    if sel_tile is not None:
        ok = _and(ok, sel_tile.astype(jnp.int32) != 0)
    return ok


# ---------------------------------------------------------------------------
# the causal grid: which score blocks are visited, in which order
# ---------------------------------------------------------------------------

class CausalBlockPlan(NamedTuple):
    """The (q-block j, kv-block kk) score blocks a causal call visits,
    each as (j, kk, interior), in the two orders its kernels walk them.
    ``q_major`` (forward, dq): a q block's kv blocks side by side, first
    to diagonal.  ``kv_major`` (dkv): a kv block's q blocks side by
    side, diagonal to last; a kv block no row reaches (sk > sq) keeps
    one fully-masked visit of the last q block, so that its dk/dv are
    written (zeros).  ``interior``: the block lies wholly under the
    diagonal and holds no padding, so its mask masks nothing — the
    kernels still run their one masked body there (a mask-free body
    gained nothing on the chip: PERF.md section 6, PR 30).  The counts
    are of ``q_major`` against the nq x nk rectangle: ``not_visited``
    blocks cost no grid step."""
    nq: int
    nk: int
    q_major: Tuple[Tuple[int, int, bool], ...]
    kv_major: Tuple[Tuple[int, int, bool], ...]
    interior: int
    diagonal: int
    not_visited: int


@functools.lru_cache(maxsize=None)
def causal_block_plan(sq: int, sk: int, bq: int, bk: int
                      ) -> CausalBlockPlan:
    """The blocks a causal call of true lengths (sq, sk) tiled (bq, bk)
    visits.  Pure and static — the geometry alone decides, never a
    setting: the one place the kernels' grids, tools/kernel_bench.py
    and the tests read."""
    nq, nk = -(-sq // bq), -(-sk // bk)

    def pair(j, kk):
        interior = ((kk + 1) * bk - 1 <= j * bq      # under the diagonal
                    and (kk + 1) * bk <= sk and (j + 1) * bq <= sq)
        return j, kk, interior

    q_major = tuple(
        pair(j, kk) for j in range(nq)
        for kk in range(min(nk - 1, ((j + 1) * bq - 1) // bk) + 1))
    kv_major = tuple(
        pair(j, kk) for kk in range(nk)
        for j in range(max(0, min(nq - 1, (kk * bk) // bq)), nq))
    interior = sum(p[2] for p in q_major)
    return CausalBlockPlan(nq, nk, q_major, kv_major, interior,
                           len(q_major) - interior,
                           nq * nk - len(q_major))


def _launch(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
            args, steps=()):
    """One flash kernel launch.  Rectangular: ``grid`` = (rows, outer,
    inner), the inner axis sequential.  Causal: ``steps`` = two int32
    vectors with one entry per visited block (``_causal_steps``),
    scalar-prefetched; the two block axes flatten into ONE sequential
    axis over just those blocks — grid (rows, len(steps[0])) — and the
    index maps and the body look their block up in the vectors, so a
    block above the diagonal costs no grid step at all."""
    if steps:
        grid = (grid[0], steps[0].shape[0])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",)),
        interpret=interpret_mode(),
        name=name,
    )(*steps, *args)


def _causal_steps(pairs):
    """(outer, inner) block indices, one pair per grid step -> the two
    int32 vectors ``_launch`` prefetches."""
    return tuple(jnp.asarray(col, jnp.int32) for col in zip(*pairs))


def _on_steps(index_map):
    """An index map written over a rectangular grid's (row, outer,
    inner) -> the same map on the flattened causal grid, where a step
    looks its (outer, inner) up in the two prefetched vectors."""
    return lambda i, t, outer, inner: index_map(i, outer[t], inner[t])


def _grid_position(flat, refs):
    """(row, outer, inner) of this grid step, and the refs that are
    left: the program ids on a rectangle; on the flattened causal grid
    the step's entry in the two prefetched vectors, which lead
    ``refs``."""
    i = pl.program_id(0)
    if not flat:
        return i, pl.program_id(1), pl.program_id(2), refs
    t = pl.program_id(1)
    return i, refs[0][t], refs[1][t], refs[2:]


# ---------------------------------------------------------------------------
# forward kernel: grid (B*H, NQ, NK), KV innermost, flash-2 online softmax
# (causal: grid (B*H, visited blocks), a q block's kv blocks side by side)
# ---------------------------------------------------------------------------

def _fwd_kernel(scale, causal, seg, sel, need_lse, rate, sq, sk, sqp, skp,
                bq, bk, nk, *refs):
    # causal: this step's block comes from the plan's q-major list
    i, j, kk, refs = _grid_position(causal, refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if rate > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    qs_ref, ks_ref, sel_ref, rest = _mask_refs(seg, sel, refs)
    if need_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        lse_ref = None

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: last KV block this Q block attends to (diagonal block)
    kk_last = jnp.minimum(nk - 1, ((j + 1) * bq - 1) // bk) if causal \
        else nk - 1

    def _body():
        # native-dtype operands on the MXU (bf16 runs at full rate),
        # f32 accumulation via preferred_element_type
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        ok = _mask_for_block(
            j, kk, bq, bk, sq, sk, sqp, skp, causal,
            qs_ref[0] if seg else None,
            ks_ref[0, :1, :] if seg else None,
            sel_ref[0] if sel else None, mask_rows=False)
        if ok is not None:
            s = jnp.where(ok, s, _NEG)
        m_prev = m_scr[:, :1]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        if rate > 0.0:
            # dropout on softmax PROBS: the denominator l uses the
            # undropped p (softmax normalizes first); only the V
            # accumulation sees the mask, scaled by 1/keep
            keep = _dropout_keep_block(seed_ref[0], i, j, kk, bq, bk,
                                       rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        pv = _dot(p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))
        acc_scr[...] = acc_scr[...] * alpha + pv

    if causal:      # every step of the flattened grid has work
        _body()
    else:
        pl.when(kk <= kk_last)(_body)

    @pl.when(kk == kk_last)
    def _finish():
        l = l_scr[:, :1]
        linv = jnp.where(l > 0.0, 1.0 / l, 0.0)
        o_ref[0] = (acc_scr[...] * linv).astype(o_ref.dtype)
        if need_lse:   # inference skips the 128-lane lse write entirely
            lse_ref[0] = jnp.where(l_scr[...] > 0.0,
                                   m_scr[...] + jnp.log(l_scr[...]),
                                   _NEG)


def _fwd_kernel_1kv(scale, causal, seg, sel, need_lse, rate, sq, sk, sqp,
                    skp, bq, bk, *refs):
    """Forward body for the nk == 1 geometry (the whole padded KV range
    fits one block, i.e. sk <= the sequence-block cap — the common
    short-sequence regime, s<=512 at d<=128 by default).

    Online softmax exists to merge partial KV blocks; with a single
    block it degenerates to dead work the generic kernel still pays:
    three VMEM scratch accumulators, three @pl.when phases per grid
    step, an alpha-rescale of the (BQ, DP) accumulator and the (BQ,
    LANES) broadcast m/l writes.  This body is the plain fused-softmax
    attention computed in registers — measured motivation: round-4's
    bf16 flash FORWARD lost to the unfused oracle at s=512 (VERDICT r4
    weak #4) while the backward won."""
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if rate > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    qs_ref, ks_ref, sel_ref, rest = _mask_refs(seg, sel, refs)
    if need_lse:
        o_ref, lse_ref = rest
    else:
        (o_ref,) = rest
        lse_ref = None
    i = pl.program_id(0)
    j = pl.program_id(1)

    s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
    ok = _mask_for_block(
        j, 0, bq, bk, sq, sk, sqp, skp, causal,
        qs_ref[0] if seg else None,
        ks_ref[0, :1, :] if seg else None,
        sel_ref[0] if sel else None, mask_rows=False)
    if ok is not None:
        s = jnp.where(ok, s, _NEG)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)       # fully-masked rows: m=_NEG, p=1
    l = jnp.sum(p, axis=1, keepdims=True)
    if rate > 0.0:
        keep = _dropout_keep_block(seed_ref[0], i, j, 0, bq, bk, rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    pv = _dot(p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))
    linv = jnp.where(l > 0.0, 1.0 / l, 0.0)
    o_ref[0] = (pv * linv).astype(o_ref.dtype)
    if need_lse:   # same layout as the generic kernel: bwd shares it
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(l > 0.0, m + jnp.log(l), _NEG),
            lse_ref.shape[1:])


def _kv_row(i, h, hk):
    """Flat KV row for flat q row ``i`` under grouped-query attention:
    q head y attends kv head y // (h // hk).  Identity when hk == h."""
    return (i // h) * hk + (i % h) // (h // hk)


# ---------------------------------------------------------------------------
# fused attention dropout: counter-based hash mask
# ---------------------------------------------------------------------------
#
# The reference fuses probability dropout into its attention kernels
# (apex/contrib/csrc/multihead_attn, fmha).  The TPU-native analog is a
# COUNTER-BASED mask: murmur3's fmix32 avalanche on the global
# (batch*head, row, col) coordinates, pure int32 vector ops.  The same
# jnp code runs inside the Pallas kernels (interpret AND Mosaic), in
# the XLA fallback path, and in the test oracle, so every path drops
# the exact same elements — and the three backward/forward kernels
# reconstruct the mask from coordinates instead of storing an
# (Sq, Sk) mask tensor anywhere.

def _fmix32(h):
    """murmur3 finalizer on int32 (wraparound semantics everywhere)."""
    h = jnp.asarray(h, jnp.int32)
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * jnp.int32(-2048144789)          # 0x85EBCA6B
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * jnp.int32(-1028477387)          # 0xC2B2AE35
    h = h ^ jax.lax.shift_right_logical(h, 16)
    return h


def _keep_mask(seed, i_flat, rows, cols, rate):
    """Boolean keep-mask for attention-prob dropout.

    seed: traced int32 scalar; i_flat: flat batch*head row (scalar);
    rows/cols: int32 arrays of GLOBAL q/k positions (any shape);
    rate: static python float in [0, 1).  keep prob = 1 - rate,
    decided by an unsigned compare of the hashed coordinates."""
    h0 = _fmix32(jnp.asarray(seed, jnp.int32)
                 + jnp.asarray(i_flat, jnp.int32) * jnp.int32(-1640531527))
    h = _fmix32(h0
                + rows.astype(jnp.int32) * jnp.int32(-1654467297)
                + cols.astype(jnp.int32) * jnp.int32(2024237689))
    # unsigned compare in int32: flip the sign bit of both sides
    # host math on the STATIC rate (per contract above), not a traced
    # concretization
    # apexlint: disable-next=APX101
    thresh = min(int((1.0 - rate) * 4294967296.0), 4294967295)
    tu = thresh ^ 0x80000000
    t = jnp.int32(tu - (1 << 32) if tu >= (1 << 31) else tu)
    return (h ^ jnp.int32(-2147483648)) < t


def _dropout_keep_block(seed, i_flat, j, kk, bq, bk, rate):
    """Keep-mask for one (BQ, BK) score block at q-block ``j`` /
    kv-block ``kk`` of flat row ``i_flat`` — the same global
    coordinates in every kernel (fwd, dq, dkv), so all three
    reconstruct the identical mask from position alone."""
    row_g = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col_g = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _keep_mask(seed, i_flat, row_g, col_g, rate)


def _sel_input(key_mask, sqp, skp):
    """The per-query key selection (B, Sq, Sk), any dtype, non-zero =
    attend, as the int8 array the kernels read a (BQ, BK) tile of per
    score block; padding selects nothing."""
    m = (key_mask != 0).astype(jnp.int8)
    return jnp.pad(m, ((0, 0), (0, sqp - m.shape[1]),
                       (0, skp - m.shape[2])))


def _fwd_pallas(q, k, v, scale, causal, segment_ids, need_lse=True,
                rate=0.0, seed=None, key_mask=None):
    b, h, sq, sk, d, dp, bq, bk, sqp, skp = _geom(q, k)
    nq, nk = sqp // bq, skp // bk
    hk = k.shape[1]
    dv, dvp = v.shape[3], _round_up(v.shape[3], _LANES)

    q3 = _pad_head(_pad_seq(q, sqp), dp).reshape(b * h, sqp, dp)
    k3 = _pad_head(_pad_seq(k, skp), dp).reshape(b * hk, skp, dp)
    v3 = _pad_head(_pad_seq(v, skp), dvp).reshape(b * hk, skp, dvp)

    # the index maps are written over (i, j, kk); on the flattened
    # causal grid (nk > 1) ``at`` looks (j, kk) up in the plan's vectors
    steps, at = (), (lambda f: f)
    if causal and nk > 1:
        steps = _causal_steps(
            p[:2] for p in causal_block_plan(sq, sk, bq, bk).q_major)
        at = _on_steps
    _kv_idx = lambda i, j, kk: (_kv_row(i, h, hk), kk, 0)
    in_specs = [
        pl.BlockSpec((1, bq, dp), at(lambda i, j, kk: (i, j, 0))),
        pl.BlockSpec((1, bk, dp), at(_kv_idx)),
        pl.BlockSpec((1, bk, dvp), at(_kv_idx)),
    ]
    args = [q3, k3, v3]
    if rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    seg = segment_ids is not None
    if seg:
        qs, ks = _seg_inputs(segment_ids, b, sqp, skp)
        in_specs += [
            pl.BlockSpec((1, bq, _LANES),
                         at(lambda i, j, kk: (i // h, j, 0))),
            pl.BlockSpec((1, 8, bk),
                         at(lambda i, j, kk: (i // h, 0,
                                              _kv_idx(i, j, kk)[1]))),
        ]
        args += [qs, ks]
    sel = key_mask is not None
    if sel:     # one tile per score block, the same for every head
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), at(lambda i, j, kk: (i // h, j, kk))))
        args.append(_sel_input(key_mask, sqp, skp))

    out_specs = [pl.BlockSpec((1, bq, dvp),
                              at(lambda i, j, kk: (i, j, 0)))]
    out_shape = [jax.ShapeDtypeStruct((b * h, sqp, dvp), q.dtype)]
    if need_lse:
        out_specs.append(
            pl.BlockSpec((1, bq, _LANES), at(lambda i, j, kk: (i, j, 0))))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, sqp, _LANES), jnp.float32))
    if nk == 1:
        kernel = functools.partial(_fwd_kernel_1kv, scale, causal, seg,
                                   sel, need_lse, rate, sq, sk, sqp,
                                   skp, bq, bk)
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, scale, causal, seg, sel,
                                   need_lse, rate, sq, sk, sqp, skp,
                                   bq, bk, nk)
        scratch = [
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, dvp), jnp.float32),
        ]
    outs = _launch(kernel, "apex_flash_attention_fwd", (b * h, nq, nk),
                   in_specs, out_specs, out_shape, scratch, args, steps)
    o = outs[0].reshape(b, h, sqp, dvp)[:, :, :sq, :dv]
    return o, (outs[1] if need_lse else None)


# ---------------------------------------------------------------------------
# backward kernels: dq over the KV grid, dk/dv over the Q grid
# ---------------------------------------------------------------------------

def _mask_refs(seg, sel, refs):
    """(qs_ref, ks_ref, sel_ref, the refs that follow): the segment-id
    pair and the key-selection tile, each there only when its flag is
    set."""
    qs_ref = ks_ref = sel_ref = None
    if seg:
        qs_ref, ks_ref, refs = refs[0], refs[1], refs[2:]
    if sel:
        sel_ref, refs = refs[0], refs[1:]
    return qs_ref, ks_ref, sel_ref, refs


def _recompute_p(scale, causal, seg, sq, sk, sqp, skp, bq, bk, j, kk,
                 q_ref, k_ref, qs_ref, ks_ref, lse_ref, sel_ref=None):
    s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
    p = jnp.exp(s - lse_ref[0, :, :1])
    ok = _mask_for_block(
        j, kk, bq, bk, sq, sk, sqp, skp, causal,
        qs_ref[0] if seg else None,
        ks_ref[0, :1, :] if seg else None,
        None if sel_ref is None else sel_ref[0], mask_rows=True)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    return p


def _dq_kernel(scale, causal, seg, sel, rate, sq, sk, sqp, skp, bq, bk,
               nk, *refs):
    # causal: this step's block comes from the plan's q-major list
    i, j, kk, refs = _grid_position(causal, refs)
    if rate > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref = refs[:6]
    qs_ref, ks_ref, sel_ref, refs = _mask_refs(seg, sel, refs[6:])
    dq_ref, dq_scr = refs

    @pl.when(kk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    kk_last = jnp.minimum(nk - 1, ((j + 1) * bq - 1) // bk) if causal \
        else nk - 1

    def _body():
        p = _recompute_p(scale, causal, seg, sq, sk, sqp, skp, bq, bk,
                         j, kk, q_ref, k_ref, qs_ref, ks_ref, lse_ref,
                         sel_ref)
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))
        if rate > 0.0:
            # dP = mask . (dO V^T)/keep; the rowsum correction stays di
            # (see _flash docstring: rowsum(dP.P) == rowsum(dO.O))
            keep = _dropout_keep_block(seed_ref[0], i, j, kk, bq, bk,
                                       rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        ds = p * (dp - di_ref[0, :, :1]) * scale
        dq_scr[...] += _dot(ds.astype(k_ref.dtype), k_ref[0],
                            ((1,), (0,)))

    if causal:      # every step of the flattened grid has work
        _body()
    else:
        pl.when(kk <= kk_last)(_body)

    @pl.when(kk == kk_last)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(scale, causal, seg, sel, rate, h, hk, sq, sk, sqp, skp,
                bq, bk, nq, g, *refs):
    """dk/dv accumulation.  The sequential axis ``t`` covers the whole
    q-head GROUP sharing this kv head times the q blocks (t = qh*NQ+j,
    grouped-query attention): every q head's contribution lands in the
    same scratch accumulator, race-free because the axis is
    'arbitrary' (sequential).  g == 1 recovers plain MHA exactly.
    Causal: the plan's kv-major list gives each step its (kk, t); a kv
    block's steps stay side by side, q heads outermost among them."""
    i, kk, t, refs = _grid_position(causal, refs)
    if rate > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref = refs[:6]
    qs_ref, ks_ref, sel_ref, refs = _mask_refs(seg, sel, refs[6:])
    dk_ref, dv_ref, dk_scr, dv_scr = refs
    j = t % nq if g > 1 else t

    # causal: first Q block whose rows reach this KV block (same for
    # every q head in the group, so init fires on the group's first
    # executed tick: qh == 0, j == j_first)
    j_first = jnp.minimum(nq - 1, (kk * bk) // bq) if causal else 0

    @pl.when(t == j_first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        p = _recompute_p(scale, causal, seg, sq, sk, sqp, skp, bq, bk,
                         j, kk, q_ref, k_ref, qs_ref, ks_ref, lse_ref,
                         sel_ref)
        if rate > 0.0:
            # the mask was drawn per FLAT Q row in fwd/dq; this grid
            # runs over kv heads, so recover that row from (i, t)
            i_flatq = (i // hk) * h + (i % hk) * g + t // nq
            keep = _dropout_keep_block(seed_ref[0], i_flatq, j, kk,
                                       bq, bk, rate)
            inv = 1.0 / (1.0 - rate)
            p_d = jnp.where(keep, p * inv, 0.0)
        else:
            p_d = p
        # dv += (dropped p)^T @ do   (contract the q dim)
        dv_scr[...] += _dot(p_d.astype(do_ref.dtype), do_ref[0],
                            ((0,), (0,)))
        dp = _dot(do_ref[0], v_ref[0], ((1,), (1,)))
        if rate > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - di_ref[0, :, :1]) * scale
        dk_scr[...] += _dot(ds.astype(q_ref.dtype), q_ref[0],
                            ((0,), (0,)))

    if causal:      # every step of the flattened grid has work
        _body()
    else:
        pl.when(j >= j_first)(_body)

    @pl.when(t == g * nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, scale, causal, segment_ids,
                rate=0.0, seed=None, key_mask=None):
    b, h, sq, sk, d, dp, bq, bk, sqp, skp = _geom(q, k)
    nq, nk = sqp // bq, skp // bk
    hk = k.shape[1]
    g = h // hk
    seed_specs, seed_args = [], []
    if rate > 0.0:
        seed_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        seed_args = [jnp.asarray(seed, jnp.int32).reshape(1)]

    dvp = _round_up(v.shape[3], _LANES)
    q3 = _pad_head(_pad_seq(q, sqp), dp).reshape(b * h, sqp, dp)
    k3 = _pad_head(_pad_seq(k, skp), dp).reshape(b * hk, skp, dp)
    v3 = _pad_head(_pad_seq(v, skp), dvp).reshape(b * hk, skp, dvp)
    do3 = _pad_head(_pad_seq(do, sqp), dvp).reshape(b * h, sqp, dvp)

    # di = rowsum(do * o): plain-XLA elementwise; both di and the saved
    # one-lane lse are broadcast to the kernel's 128-lane layout so
    # neither bwd kernel ever transposes
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.pad(di.reshape(b * h, sq), ((0, 0), (0, sqp - sq)))
    di = jnp.broadcast_to(di[:, :, None], (b * h, sqp, _LANES))
    if lse.shape[1] != sqp:     # callers may pass unpadded (b*h, Sq)
        lse = jnp.pad(lse, ((0, 0), (0, sqp - lse.shape[1])))
    lse = jnp.broadcast_to(lse[:, :, None], (b * h, sqp, _LANES))

    seg = segment_ids is not None

    # dkv grid rows run over KV heads (b*hk); its sequential axis t
    # covers the q-head group x q blocks.  These maps recover the flat
    # q row and the q block from (i, kk, t).
    def _q_row_kv(i, t):
        return (i // hk) * h + (i % hk) * g + t // nq

    # index maps are written over the rectangular grids' positions —
    # (i, j, kk) for dq, (i, kk, t) for dkv; on the flattened causal
    # grids ``at`` looks the block up in the plan's vectors (q-major
    # for dq; kv-major for dkv, the q-head group unrolled into t as on
    # the rectangle)
    dq_steps, dkv_steps, at = (), (), (lambda f: f)
    if causal:
        plan = causal_block_plan(sq, sk, bq, bk)
        dq_steps = _causal_steps(p[:2] for p in plan.q_major)
        kt = []
        for kk, grp in itertools.groupby(plan.kv_major,
                                         key=lambda p: p[1]):
            js = [p[0] for p in grp]
            kt += [(kk, qh * nq + j) for qh in range(g) for j in js]
        dkv_steps = _causal_steps(kt)
        at = _on_steps
    _kv_idx = lambda i, j, kk: (_kv_row(i, h, hk), kk, 0)
    _q_idx_kv = lambda i, kk, t: (_q_row_kv(i, t), t % nq, 0)
    base_specs = [
        pl.BlockSpec((1, bq, dp), at(lambda i, j, kk: (i, j, 0))),
        pl.BlockSpec((1, bk, dp), at(_kv_idx)),
        pl.BlockSpec((1, bk, dvp), at(_kv_idx)),
        pl.BlockSpec((1, bq, dvp), at(lambda i, j, kk: (i, j, 0))),
        pl.BlockSpec((1, bq, _LANES), at(lambda i, j, kk: (i, j, 0))),
        pl.BlockSpec((1, bq, _LANES), at(lambda i, j, kk: (i, j, 0))),
    ]
    args = [q3, k3, v3, do3, lse, di]
    seg_specs = []
    if seg:
        qs, ks = _seg_inputs(segment_ids, b, sqp, skp)
        seg_specs = [
            pl.BlockSpec((1, bq, _LANES),
                         at(lambda i, j, kk: (i // h, j, 0))),
            pl.BlockSpec((1, 8, bk),
                         at(lambda i, j, kk: (i // h, 0,
                                              _kv_idx(i, j, kk)[1]))),
        ]
        args += [qs, ks]
    sel = key_mask is not None
    sel_specs = []
    if sel:
        sel_specs = [pl.BlockSpec(
            (1, bq, bk), at(lambda i, j, kk: (i // h, j, kk)))]
        args.append(_sel_input(key_mask, sqp, skp))

    dq = _launch(
        functools.partial(_dq_kernel, scale, causal, seg, sel, rate, sq,
                          sk, sqp, skp, bq, bk, nk),
        "apex_flash_attention_dq", (b * h, nq, nk),
        seed_specs + base_specs + seg_specs + sel_specs,
        [pl.BlockSpec((1, bq, dp), at(lambda i, j, kk: (i, j, 0)))],
        [jax.ShapeDtypeStruct((b * h, sqp, dp), q.dtype)],
        [pltpu.VMEM((bq, dp), jnp.float32)],
        seed_args + args, dq_steps)[0]

    # dk/dv grid: (BH, NK, NQ) — q innermost; index maps swap j/kk roles
    kv_specs = [
        pl.BlockSpec((1, bq, dp), at(_q_idx_kv)),
        pl.BlockSpec((1, bk, dp), at(lambda i, kk, j: (i, kk, 0))),
        pl.BlockSpec((1, bk, dvp), at(lambda i, kk, j: (i, kk, 0))),
        pl.BlockSpec((1, bq, dvp), at(_q_idx_kv)),
        pl.BlockSpec((1, bq, _LANES), at(_q_idx_kv)),
        pl.BlockSpec((1, bq, _LANES), at(_q_idx_kv)),
    ]
    if seg:
        kv_specs += [
            pl.BlockSpec((1, bq, _LANES),
                         at(lambda i, kk, t: (
                             i // hk, _q_idx_kv(i, kk, t)[1], 0))),
            pl.BlockSpec((1, 8, bk),
                         at(lambda i, kk, t: (i // hk, 0, kk))),
        ]
    if sel:
        kv_specs.append(pl.BlockSpec(
            (1, bq, bk), at(lambda i, kk, t: (i // hk, t % nq, kk))))
    dk, dv = _launch(
        functools.partial(_dkv_kernel, scale, causal, seg, sel, rate, h,
                          hk, sq, sk, sqp, skp, bq, bk, nq, g),
        "apex_flash_attention_dkv", (b * hk, nk, g * nq),
        seed_specs + kv_specs,
        [pl.BlockSpec((1, bk, dp), at(lambda i, kk, t: (i, kk, 0))),
         pl.BlockSpec((1, bk, dvp), at(lambda i, kk, t: (i, kk, 0)))],
        [jax.ShapeDtypeStruct((b * hk, skp, dp), k.dtype),
         jax.ShapeDtypeStruct((b * hk, skp, dvp), v.dtype)],
        [pltpu.VMEM((bk, dp), jnp.float32),
         pltpu.VMEM((bk, dvp), jnp.float32)],
        seed_args + args, dkv_steps)

    dq = dq.reshape(b, h, sqp, dp)[:, :, :sq, :d]
    dk = dk.reshape(b, hk, skp, dp)[:, :, :sk, :d]
    dv = dv.reshape(b, hk, skp, dvp)[:, :, :sk, :v.shape[3]]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, segment_ids, seed, causal, scale, rate):
    # primal (non-differentiated) path: no lse output at all
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    o, _ = _fwd_pallas(q, k, v, sc, causal, segment_ids,
                       need_lse=False, rate=rate, seed=seed)
    return o


def _flash_fwd(q, k, v, segment_ids, seed, causal, scale, rate):
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    o, lse = _fwd_pallas(q, k, v, sc, causal, segment_ids,
                         rate=rate, seed=seed)
    # keep ONE lane of the kernel's 128-lane lse layout as the residual
    # (they're identical); _bwd_pallas re-broadcasts.  The dropout mask
    # is NOT a residual: every backward kernel reconstructs it from the
    # (seed, coordinates) hash.
    return o, (q, k, v, segment_ids, seed, o, lse[:, :, 0])


def _flash_bwd(causal, scale, rate, res, do):
    q, k, v, segment_ids, seed, o, lse = res
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    dq, dk, dv = _bwd_pallas(q, k, v, o, lse, do, sc, causal,
                             segment_ids, rate=rate, seed=seed)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_selected(q, k, v, key_mask, causal, scale):
    """The same kernels under a per-query key selection -> (o, lse):
    ``lse`` (B, H, Sq) float32 is each row's log-sum-exp over the keys
    it attends (-1e30 for a row that attends none), what the kernels
    save for their backward pass; no gradient flows through it."""
    return _flash_selected_fwd(q, k, v, key_mask, causal, scale)[0]


def _flash_selected_fwd(q, k, v, key_mask, causal, scale):
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    o, lse = _fwd_pallas(q, k, v, sc, causal, None, key_mask=key_mask)
    lse = lse[:, :, 0]
    b, h, sq = q.shape[:3]
    return ((o, lse.reshape(b, h, -1)[:, :, :sq]),
            (q, k, v, key_mask, o, lse))


def _flash_selected_bwd(causal, scale, res, cts):
    q, k, v, key_mask, o, lse = res
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    dq, dk, dv = _bwd_pallas(q, k, v, o, lse, cts[0], sc, causal, None,
                             key_mask=key_mask)
    return dq, dk, dv, None


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def dropout_seed_from_key(key):
    """Fold a jax PRNG key down to the int32 seed the fused hash-mask
    dropout consumes (deterministic per key, traced).  THE one
    canonical fold: every frontend (contrib.multihead_attn,
    contrib.fmha, user code) must derive seeds this way so the same
    key always drops the same elements."""
    return jax.random.randint(key, (), 0, 2147483647, dtype=jnp.int32)


def dropout_keep_ref(seed, b, h, sq, sk, rate):
    """(B, H, Sq, Sk) keep-mask EXACTLY matching the kernels' hash
    (same _keep_mask on global coordinates).  Used by the XLA fallback
    path and the test oracle, so dropout semantics are dispatch-stable:
    the kernel and the escape hatch drop the same elements."""
    i = jnp.arange(b * h, dtype=jnp.int32)[:, None, None]
    rows = jnp.arange(sq, dtype=jnp.int32)[None, :, None]
    cols = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
    keep = _keep_mask(jnp.asarray(seed, jnp.int32).reshape(()),
                      i, rows, cols, rate)
    return keep.reshape(b, h, sq, sk)


def _dense_fallback_fits(q_shape, k_shape) -> bool:
    """Memory gate on the unfused escape hatch: the dense path
    materializes the (B, H, Sq, Sk) f32 score tensor (several live
    copies under remat — the round-4 window hit a 48G HBM request at
    s=8192 on a 16G chip when measured prefs routed attention to XLA).
    The measured preference table only speaks for the shapes the bench
    ran (Sq·Sk <= 2048²); past this element budget the flash kernel is
    the only memory-safe implementation and the preference is ignored.
    Operator overrides (APEX_TPU_DISABLE_PALLAS, APEX_TPU_PREFER_XLA)
    are NOT subject to this gate — see _dispatch.prefs_disabled.
    """
    b, h, sq = q_shape[0], q_shape[1], q_shape[2]
    sk = k_shape[2]
    env = os.environ.get("APEX_TPU_ATTN_DENSE_MAX_SCORES")
    budget = 2 ** 27
    if env:
        try:
            iv = int(env)
        except ValueError:
            iv = -1
        if iv > 0:
            budget = iv
        else:
            import warnings
            warnings.warn(
                f"APEX_TPU_ATTN_DENSE_MAX_SCORES={env!r} is not a "
                f"positive integer; using the default budget {budget}")
    return b * h * sq * sk <= budget


def packed_segment_ids(segment_ids, xp=jnp):
    """(q_ids, kv_ids) for a packed batch's base segment array
    ((B, S); 1.. per sequence, 0 on padding — the
    apex_tpu.data.pack_sequences form).  Padding gets DISJOINT ids per
    side (-1 on q, -2 on kv, the contrib.fmha convention) so pad rows
    attend nowhere and output exact zeros.  The single home of that
    convention — data.pack_sequences (xp=numpy, host side) and the
    GPT packed path (traced) both derive from here."""
    return (xp.where(segment_ids > 0, segment_ids, -1),
            xp.where(segment_ids > 0, segment_ids, -2))


@jax.named_scope("apex_attention")
def flash_attention(q, k, v, causal=False, scale=None,
                    segment_ids: Optional[Tuple[jax.Array,
                                                jax.Array]] = None,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    key_mask: Optional[jax.Array] = None,
                    return_lse: bool = False):
    """Fused scaled-dot-product attention, (B, H, S, D) layout.

    Replaces the reference's fast_multihead_attn softmax-chain kernels
    and fmhalib (SURVEY.md §2.3): same math, one K-tiled online-softmax
    kernel, no HBM score materialization at any sequence length.

    segment_ids: optional (q_ids (B, Sq), kv_ids (B, Sk)) int arrays;
    attention is masked where ids differ (packed variable-length
    batches — the fmha contract).

    Grouped-query / multi-query attention (beyond-reference TPU
    extension): k/v may carry FEWER heads than q — (B, HK, Sk, D) with
    H % HK == 0; q head y attends kv head y // (H // HK).  The kernels
    read the small K/V straight from HBM (the bandwidth point of GQA)
    instead of materializing repeated heads.

    dropout_rate/dropout_seed: fused probability dropout (the
    reference fuses it in multihead_attn/fmha kernels).  rate is a
    STATIC float in [0, 1); seed is a traced int32 scalar (vary it per
    step).  The mask is a counter-based hash of (seed, head, row, col)
    recomputed inside every kernel — no mask tensor is ever stored —
    and the backward drops the same elements.  Callers own the
    train/eval switch: pass rate 0 (or no seed) when not training.

    v may have a head width of its own, (B, HK, Sk, Dv) (latent
    attention: scores at q/k's width, values at another): the output
    and the dv accumulator are at Dv's, q.k at D's, v is padded to a
    lane multiple of its own width only.

    key_mask: optional (B, Sq, Sk) per-query key selection, non-zero
    where query row t attends key s, the same for every head (learned
    sparse attention: ``ops/sparse_index.py`` makes one).  The softmax
    and both backward kernels run over the selected keys only — a tile
    of the mask is read per score block beside ``causal``, which still
    prunes the blocks above the diagonal; a block whose tile selects
    nothing is computed all the same.  A row that selects nothing gives
    zeros.  Not combined with ``segment_ids`` or dropout, and always on
    the kernels (a dense form of a selection has no use at the lengths
    a selection is for).  ``return_lse`` (with ``key_mask``) returns
    ``(o, lse)``, ``lse`` (B, H, Sq) float32 the rows' log-sum-exp over
    their selected keys, constant under differentiation.
    """
    h, hk = q.shape[1], k.shape[1]
    if v.shape[:3] != k.shape[:3] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"flash_attention: k {k.shape} and v {v.shape} must share "
            f"(B, HK, Sk), and q {q.shape} k's head width")
    if h % hk:
        raise ValueError(
            f"flash_attention: q heads ({h}) must be a multiple of kv "
            f"heads ({hk})")
    rate = float(dropout_rate)
    if not 0.0 <= rate < 1.0:
        raise ValueError(
            f"flash_attention: dropout_rate must be in [0, 1), got "
            f"{dropout_rate!r}")
    if rate > 0.0 and dropout_seed is None:
        raise ValueError(
            "flash_attention: dropout_rate > 0 requires dropout_seed "
            "(a traced int32 scalar; vary it per training step)")
    seed = (None if rate == 0.0
            else jnp.asarray(dropout_seed, jnp.int32).reshape(()))
    # the kernels dot native-dtype operands (full-rate MXU): normalize
    # mixed q/k/v dtypes once here so kernel and fallback paths agree
    if not (q.dtype == k.dtype == v.dtype):
        dt = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype),
                               v.dtype)
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    if key_mask is not None:
        if segment_ids is not None or rate > 0.0:
            raise ValueError(
                "flash_attention: key_mask is not combined with "
                "segment_ids or dropout")
        if key_mask.shape != (q.shape[0], q.shape[2], k.shape[2]):
            raise ValueError(
                f"flash_attention: key_mask must be (B, Sq, Sk) = "
                f"{(q.shape[0], q.shape[2], k.shape[2])}, got "
                f"{key_mask.shape}")
        o, lse = _flash_selected(q, k, v, key_mask, causal, scale)
        return (o, jax.lax.stop_gradient(lse)) if return_lse else o
    if return_lse:
        raise ValueError("flash_attention: return_lse goes with key_mask")
    fam = _attn_family(q.dtype)
    if not op_enabled(fam) and not (
            _dispatch.prefs_disabled(fam)
            and not _dense_fallback_fits(q.shape, k.shape)):
        sc = scale if scale is not None else _default_scale(q.shape[-1])
        # jax.checkpoint: don't hold the (Sq, Sk) probability residual
        # between fwd and bwd on the escape-hatch path
        ref = jax.checkpoint(functools.partial(
            attention_ref, causal=causal, scale=sc,
            dropout_rate=rate, dropout_seed=seed))
        if segment_ids is not None:
            q_ids, kv_ids = segment_ids
            same = q_ids[:, None, :, None] == kv_ids[:, None, None, :]
            o = ref(q, k, v, mask=jnp.where(same, 0.0, _NEG))
            # kernel contract: fully-masked q rows give exact zeros (the
            # oracle's softmax over an all--1e30 row gives mean-of-V);
            # under causal, positions above the diagonal don't count as
            # visible either
            visible = same
            if causal:
                sq, sk = q.shape[2], k.shape[2]
                col_ok = (jnp.arange(sk)[None, :]
                          <= jnp.arange(sq)[:, None])   # (Sq, Sk)
                visible = visible & col_ok[None, None]
            any_kv = jnp.any(visible, axis=-1)          # (B, 1, Sq)
            return jnp.where(any_kv[..., None], o, 0.0).astype(q.dtype)
        return ref(q, k, v)
    return _flash(q, k, v, segment_ids, seed, causal, scale, rate)


@jax.named_scope("apex_attention")
def attention_ref(q, k, v, causal=False, scale=None,
                  mask: Optional[jax.Array] = None,
                  dropout_rate: float = 0.0, dropout_seed=None):
    """XLA oracle/fallback; mask: additive (B,1|H,Sq,Sk) or None.

    f32 inputs get HIGHEST matmul precision (true f32 on the MXU, same
    contract as the kernel's _dot); bf16 inputs keep the fast default.
    Grouped-query shapes (kv heads < q heads) are handled by repeating
    kv — the oracle states the semantics; the kernel avoids the copy.
    Dropout uses the SAME counter-based hash as the kernels
    (dropout_keep_ref), so kernel and oracle drop identical elements.
    """
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    prec = matmul_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * sc
    if mask is not None:
        s = s + mask
    if causal:
        sq, sk = s.shape[-2:]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col > row, _NEG, s)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError(
                "attention_ref: dropout_rate > 0 requires dropout_seed "
                "(a traced int32 scalar; vary it per training step)")
        b, h, sq, sk = p.shape
        keep = dropout_keep_ref(dropout_seed, b, h, sq, sk,
                                dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=prec).astype(q.dtype)


# ---------------------------------------------------------------------------
# blockwise partial attention with stats (building block of the ring)
# ---------------------------------------------------------------------------

def _partial_attention(q, k, v, scale, mask_val):
    """Unnormalized attention of q against ONE kv block.

    Returns (o_un (B,H,Sq,D), m (B,H,Sq), l (B,H,Sq)): o_un = exp(s-m)@v,
    l = rowsum(exp(s-m)).  mask_val: additive (Sq, Sk) or None.
    """
    prec = matmul_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * scale
    if mask_val is not None:
        s = s + mask_val
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   precision=prec)
    return o, m, l


def _block_modes(causal, kv_owner, rank):
    """0 = attend fully, 1 = diagonal (causal mask), 2 = skip."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(kv_owner < rank, 0,
                     jnp.where(kv_owner == rank, 1, 2)).astype(jnp.int32)


def _ring_block_fwd(q, k_r, v_r, sc, mode):
    """One ring step through the flash kernel: normalized block output
    + lse, switched over the causal block mode."""
    b, h, s_loc, d = q.shape

    def _run(causal_flag):
        def f(_):
            o, lse = _fwd_pallas(q, k_r, v_r, sc, causal_flag, None,
                                 need_lse=True)
            lse = lse[:, :s_loc, 0].reshape(b, h, s_loc)
            return o.astype(jnp.float32), lse
        return f

    def _skip(_):
        return (jnp.zeros((b, h, s_loc, d), jnp.float32),
                jnp.full((b, h, s_loc), _NEG, jnp.float32))

    return jax.lax.switch(mode, [_run(False), _run(True), _skip], None)


def _ring_block_bwd(q, k_r, v_r, o, lse1, do, sc, mode):
    """One backward ring step: per-block (dq, dk, dv) from the Pallas
    backward kernels evaluated against the GLOBAL lse (probabilities
    come out globally normalized, so the partials sum exactly)."""
    b, h, s_loc, d = q.shape

    def _run(causal_flag):
        def f(_):
            return _bwd_pallas(q, k_r, v_r, o, lse1, do, sc,
                               causal_flag, None)
        return f

    def _skip(_):
        return (jnp.zeros_like(q), jnp.zeros_like(k_r),
                jnp.zeros_like(v_r))

    return jax.lax.switch(mode, [_run(False), _run(True), _skip], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, causal, scale, axis):
    o, _ = _ring_fwd_impl(q, k, v, causal, scale, axis)
    return o


def _ring_fwd_impl(q, k, v, causal, scale, axis):
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    cp = comm.bound_axis_size(axis)
    # only the causal mask consumes the rank; a dead axis_index would
    # leave an unused partition-id instruction the CPU SPMD partitioner
    # rejects outright (it only rewrites the patterns it recognizes)
    rank = jax.lax.axis_index(axis) if causal else jnp.int32(0)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def step(carry, r):
        o, lse, k_r, v_r = carry
        kv_owner = (rank - r) % cp
        mode = _block_modes(causal, kv_owner, rank)
        o_i, lse_i = _ring_block_fwd(q, k_r, v_r, sc, mode)
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)
        w_new = jnp.exp(lse_i - lse_new)
        o = o * w_old[..., None] + o_i * w_new[..., None]
        k_r = jax.lax.ppermute(k_r, axis, perm)
        v_r = jax.lax.ppermute(v_r, axis, perm)
        return (o, lse_new, k_r, v_r), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    (o, lse, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v),
                                     jnp.arange(cp))
    return o.astype(q.dtype), lse


def _ring_vjp_fwd(q, k, v, causal, scale, axis):
    o, lse = _ring_fwd_impl(q, k, v, causal, scale, axis)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(causal, scale, axis, res, do):
    q, k, v, o, lse = res
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    cp = comm.bound_axis_size(axis)
    rank = jax.lax.axis_index(axis) if causal else jnp.int32(0)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    lse1 = lse.reshape(b * h, s_loc)

    # second ring: dk/dv accumulators travel WITH their kv block, so
    # after the full cycle every block is back home carrying the sum of
    # all ranks' contributions
    def step(carry, r):
        dq, k_r, v_r, dk_r, dv_r = carry
        kv_owner = (rank - r) % cp
        mode = _block_modes(causal, kv_owner, rank)
        dq_i, dk_i, dv_i = _ring_block_bwd(q, k_r, v_r, o, lse1, do,
                                           sc, mode)
        dq = dq + dq_i.astype(jnp.float32)
        dk_r = dk_r + dk_i.astype(jnp.float32)
        dv_r = dv_r + dv_i.astype(jnp.float32)
        k_r = jax.lax.ppermute(k_r, axis, perm)
        v_r = jax.lax.ppermute(v_r, axis, perm)
        dk_r = jax.lax.ppermute(dk_r, axis, perm)
        dv_r = jax.lax.ppermute(dv_r, axis, perm)
        return (dq, k_r, v_r, dk_r, dv_r), None

    zeros = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (zeros, k, v, zeros, zeros), jnp.arange(cp))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


@jax.named_scope("apex_attention")
def ring_attention(q, k, v, causal=False, scale=None,
                   axis: str = comm.AXIS_CTX):
    """Context-parallel attention: sequences sharded over ``axis``.

    q/k/v: (B, H, S/cp, D) per shard.  KV blocks rotate around the ring
    with ppermute; per-block flash-kernel calls merge via logsumexp, so
    the full (S, S) score matrix never exists anywhere and each block
    runs at kernel speed.  Backward is a second ring whose dk/dv
    accumulators travel with their KV block (each block arrives home
    after the full cycle carrying every rank's contribution).  Per-step
    traffic is the KV block (+cotangets in backward) on ICI neighbors.

    Reverse-mode only (custom_vjp): for jvp/forward-mode use
    ``ring_attention_ref`` (plain scan + ppermute, fully transposable)
    or set APEX_TPU_DISABLE_PALLAS=1.
    """
    if k.shape[1] != q.shape[1]:
        # the ring's blockwise math and its traveling dk/dv accumulators
        # are head-aligned with q; GQA shapes would half-work (forward
        # only) — refuse clearly instead.  GQA composes with
        # ulysses_attention (hk % cp == 0) or plain flash_attention.
        raise ValueError(
            f"ring_attention requires equal q/kv head counts, got "
            f"q={q.shape[1]} kv={k.shape[1]}; repeat kv heads first or "
            "use ulysses_attention / flash_attention for grouped-query "
            "shapes")
    # normalize mixed dtypes BEFORE picking the dispatch family, so
    # this entry point and flash_attention consult the same precision
    # class for identical inputs
    if not (q.dtype == k.dtype == v.dtype):
        dt = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype),
                               v.dtype)
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    if op_enabled(_attn_family(q.dtype)):
        return _ring(q, k, v, causal, scale, axis)
    return ring_attention_ref(q, k, v, causal=causal, scale=scale,
                              axis=axis)


def ring_attention_ref(q, k, v, causal=False, scale=None,
                       axis: str = comm.AXIS_CTX):
    """jnp blockwise ring (oracle/escape hatch): same math, plain XLA
    per-block attention with online stat merging.
    """
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    cp = comm.bound_axis_size(axis)
    rank = jax.lax.axis_index(axis)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    row = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)

    # jax.checkpoint: without it the scan saves every step's (s_loc,
    # s_loc) probability block as a backward residual — O(cp * s^2)
    # memory, exactly what ring attention exists to avoid.  Remat
    # recomputes each block's scores during backward instead.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def step_math(o, m, l, k_r, v_r, r):
        # k_r currently holds the block owned by rank (rank - r) mod cp
        kv_owner = (rank - r) % cp
        if causal:
            # global positions: q row i -> rank*s_loc + i; kv col j ->
            # kv_owner*s_loc + j
            qpos = rank * s_loc + row
            kpos = kv_owner * s_loc + col
            mask_val = jnp.where(kpos > qpos, _NEG, 0.0)
        else:
            mask_val = None
        o_i, m_i, l_i = _partial_attention(q, k_r, v_r, sc, mask_val)
        m_new = jnp.maximum(m, m_i)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(m_i - m_new)
        o = o * c_old[..., None] + o_i * c_new[..., None]
        l = l * c_old + l_i * c_new
        return o, m_new, l

    def step(carry, r):
        o, m, l, k_r, v_r = carry
        o, m, l = step_math(o, m, l, k_r, v_r, r)
        k_r = jax.lax.ppermute(k_r, axis, perm)
        v_r = jax.lax.ppermute(v_r, axis, perm)
        return (o, m, l, k_r, v_r), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # K/V rotate in their INPUT dtype: bf16 halves the per-step ppermute
    # bytes on ICI; _partial_attention upcasts to f32 for the math anyway
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(cp))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# all-to-all sequence parallelism (Ulysses-style) — the second
# long-context strategy next to the ppermute ring
# ---------------------------------------------------------------------------

@jax.named_scope("apex_attention")
def ulysses_attention(q, k, v, causal=False, scale=None,
                      axis: str = comm.AXIS_CTX):
    """All-to-all sequence parallelism over ``axis`` (Ulysses style).

    q/k/v: (B, H, S/cp, D) per shard, with H divisible by cp.  One
    ``all_to_all`` reshards sequence→heads — every device ends up with
    the FULL sequence for H/cp heads — the flash kernel runs ordinary
    full-sequence attention locally (causal masking is exact, positions
    are global), and a second ``all_to_all`` restores (B, H, S/cp, D).

    vs ``ring_attention``: two all_to_all collectives total (each moving
    the activations once) instead of cp ppermute rounds of KV blocks —
    cheaper when cp is large and ICI all_to_all bandwidth is good, but
    requires H % cp == 0 while the ring has no head constraint.  Both
    are beyond-reference extensions: apex's only sequence-length scaling
    is Megatron SP (SURVEY.md §2.5).

    Differentiable end to end (all_to_all transposes to all_to_all; the
    kernel brings its custom_vjp).
    """
    cp = comm.bound_axis_size(axis)
    if cp == 1:
        return flash_attention(q, k, v, causal=causal, scale=scale)
    h, hk = q.shape[1], k.shape[1]
    if h % cp or hk % cp:
        # GQA composes with Ulysses when BOTH head counts split over the
        # axis (each device then holds H/cp q heads + HK/cp kv heads of
        # the full sequence); checking only q would let hk % cp != 0
        # die inside all_to_all with an opaque shape error
        raise ValueError(
            f"ulysses_attention: q heads ({h}) and kv heads ({hk}) "
            f"must be divisible by the '{axis}' axis size ({cp}); use "
            "ring_attention for head-count-agnostic context "
            "parallelism")

    def seq_to_heads(x):   # (B, H, S/cp, D) -> (B, H/cp, S, D)
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):   # (B, H/cp, S, D) -> (B, H, S/cp, D)
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    o = flash_attention(seq_to_heads(q), seq_to_heads(k),
                        seq_to_heads(v), causal=causal, scale=scale)
    return heads_to_seq(o)
