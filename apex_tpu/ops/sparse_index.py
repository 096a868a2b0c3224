"""Learned sparse attention's indexer: which keys each query attends.

A small scorer (a few narrow heads, ONE shared key head) rates every
causal (query, key) pair; each query keeps its ``topk`` best keys; the
main attention then runs over the kept keys only
(``flash_attention(key_mask=...)``), and the scorer is trained to
follow the main attention's own distribution over them (the
"lightning indexer" of DeepSeek-V3.2-Exp's sparse attention):

    I[t, s]  = sum_j w[t, j] * relu(q_j[t] . k[s])            s <= t
    S_t      = the min(topk, t + 1) keys s <= t with the largest I[t, s]
    p[t, s]  = mean over heads of softmax_{s in S_t}(main scores)
    L_I      = mean_t  sum_{s in S_t} p[t, s] (log p[t, s]
                                   - log softmax_{s in S_t}(I[t, .])[s])

Three ops, one per line above, none of which ever holds a
(heads, S, S) array:

- ``index_scores``: a Pallas kernel per (query block, key block) tile
  that loops over the scorer's heads in VMEM; its backward is ONE
  kernel (dq and dw accumulate over a query block's key blocks, dk in
  an output that stays resident in VMEM over the whole grid).
- ``select_topk``: the exact top-k set as a mask, without a sort: a
  Pallas kernel holds a block of rows in VMEM and finds each row's k-th
  largest value by bisection over the 32 bits of the scores'
  order-preserving integer image (32 counting passes), then breaks ties
  at the threshold towards the lower index, as ``lax.top_k`` does, by
  the same bisection over the column index.
- ``index_loss``: a Pallas kernel that recomputes the main attention's
  probabilities head by head from the flash kernels' saved log-sum-exp,
  averages them in VMEM and folds the tile into each row's divergence;
  the same pass writes the gradient with respect to ``I``
  (softmax_S(I) - p), so the backward is a scaling.
  ``index_loss_and_grad`` hands out both results of that one pass, for
  a caller that pulls the gradient back to the scorer's few parameters
  where the loss is computed and keeps that instead of recomputing the
  pass (``models/sparse_moe.py``).

Scopes: ``apex_sparse_attn/indexer`` (scores, forward and backward),
``apex_sparse_attn/select``, ``apex_sparse_attn/index_loss``.
Shapes: main attention (B, H, S, D); scorer queries (B, Hi, S, Di),
its one key head (B, S, Di), its head weights (B, S, Hi) float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import interpret_mode
from apex_tpu.ops.attention import (_LANES, _NEG, _block, _default_scale,
                                    _dot, _kv_row, _round_up,
                                    matmul_precision)

_BLOCK_CAP = 512            # the attention kernels' default block
_VMEM_LIMIT = 64 << 20      # the backward holds dk for the whole sequence


def _tiles(s: int):
    blk = _block(s, _BLOCK_CAP)
    sp = _round_up(s, blk)
    return blk, sp, sp // blk


def _pad_to(x, axis, size):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _last_kv(j, bq, bk):
    """The diagonal key block of query block ``j`` (square tiling: j)."""
    return ((j + 1) * bq - 1) // bk


def _causal_tile(j, kk, bq, bk, s):
    row = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (col <= row) & (col < s)


# ---------------------------------------------------------------------------
# index scores
# ---------------------------------------------------------------------------

def _scores_kernel(hi, s, blk, q_ref, k_ref, w_ref, o_ref):
    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk <= _last_kv(j, blk, blk))
    def _tile():
        w = w_ref[0]
        acc = jnp.zeros((blk, blk), jnp.float32)
        for n in range(hi):
            sc = _dot(q_ref[0, n], k_ref[0], ((1,), (1,)))
            acc = acc + w[:, n:n + 1] * jnp.maximum(sc, 0.0)
        o_ref[0] = jnp.where(_causal_tile(j, kk, blk, blk, s), acc, _NEG)

    @pl.when(kk > _last_kv(j, blk, blk))
    def _above():
        o_ref[0] = jnp.full((blk, blk), _NEG, jnp.float32)


def _scores_bwd_kernel(hi, s, blk, q_ref, k_ref, w_ref, g_ref,
                       dq_ref, dw_ref, dk_ref, dq_scr, dw_scr):
    j, kk = pl.program_id(1), pl.program_id(2)
    last = _last_kv(j, blk, blk)

    @pl.when((j == 0) & (kk == 0))
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(kk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(kk <= last)
    def _tile():
        g = jnp.where(_causal_tile(j, kk, blk, blk, s), g_ref[0], 0.0)
        w, k = w_ref[0], k_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        dk = jnp.zeros(k.shape, jnp.float32)
        for n in range(hi):
            q = q_ref[0, n]
            sc = _dot(q, k, ((1,), (1,)))
            dw_n = jnp.sum(g * jnp.maximum(sc, 0.0), axis=1, keepdims=True)
            dw_scr[...] += jnp.where(lane == n, dw_n, 0.0)
            ds = jnp.where(sc > 0.0, g * w[:, n:n + 1], 0.0).astype(k.dtype)
            dq_scr[n] += _dot(ds, k, ((1,), (0,)))
            dk = dk + _dot(ds, q, ((0,), (0,)))
        rows = pl.ds(pl.multiple_of(kk * blk, blk), blk)
        dk_ref[0, rows, :] += dk

    @pl.when(kk == last)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_scr[...]


def _scores_operands(q, k, w):
    """Pad to the kernels' layout: sequence to a whole number of
    blocks, the scorer's head to 128 lanes (a 64-wide contraction fills
    half the MXU either way), the head weights to 128 lanes."""
    b, hi, s, di = q.shape
    blk, sp, n = _tiles(s)
    dp = _round_up(di, _LANES)
    q4 = _pad_to(_pad_to(q, 2, sp), 3, dp)
    k3 = _pad_to(_pad_to(k, 1, sp), 2, dp)
    w3 = _pad_to(_pad_to(w.astype(jnp.float32), 1, sp), 2, _LANES)
    return q4, k3, w3, (b, hi, s, di, blk, sp, n, dp)


def _scores_specs(hi, blk, dp):
    def kv(b, j, kk):
        return (b, jnp.minimum(kk, _last_kv(j, blk, blk)), 0)
    return [pl.BlockSpec((1, hi, blk, dp), lambda b, j, kk: (b, 0, j, 0)),
            pl.BlockSpec((1, blk, dp), kv),
            pl.BlockSpec((1, blk, _LANES), lambda b, j, kk: (b, j, 0))]


@jax.custom_vjp
def _index_scores(q, k, w):
    return _index_scores_fwd(q, k, w)[0]


def _index_scores_fwd(q, k, w):
    q4, k3, w3, (b, hi, s, di, blk, sp, n, dp) = _scores_operands(q, k, w)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, hi, s, blk),
        grid=(b, n, n),
        in_specs=_scores_specs(hi, blk, dp),
        out_specs=pl.BlockSpec((1, blk, blk), lambda b, j, kk: (b, j, kk)),
        out_shape=jax.ShapeDtypeStruct((b, sp, sp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="apex_index_scores_fwd",
    )(q4, k3, w3)
    return out[:, :s, :s], (q, k, w)


def _index_scores_bwd(res, g):
    q, k, w = res
    q4, k3, w3, (b, hi, s, di, blk, sp, n, dp) = _scores_operands(q, k, w)
    g3 = _pad_to(_pad_to(g.astype(jnp.float32), 1, sp), 2, sp)

    def tile(b, j, kk):
        return (b, j, jnp.minimum(kk, _last_kv(j, blk, blk)))
    dq, dw, dk = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, hi, s, blk),
        grid=(b, n, n),
        in_specs=_scores_specs(hi, blk, dp)
        + [pl.BlockSpec((1, blk, blk), tile)],
        out_specs=[
            pl.BlockSpec((1, hi, blk, dp), lambda b, j, kk: (b, 0, j, 0)),
            pl.BlockSpec((1, blk, _LANES), lambda b, j, kk: (b, j, 0)),
            pl.BlockSpec((1, sp, dp), lambda b, j, kk: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, hi, sp, dp), q.dtype),
                   jax.ShapeDtypeStruct((b, sp, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, sp, dp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hi, blk, dp), jnp.float32),
                        pltpu.VMEM((blk, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="apex_index_scores_bwd",
    )(q4, k3, w3, g3)
    return (dq[:, :, :s, :di], dk[:, :s, :di].astype(k.dtype),
            dw[:, :s, :hi].astype(w.dtype))


_index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


@jax.named_scope("apex_sparse_attn/indexer")
def index_scores(q, k, w):
    """I[b, t, s] = sum_j w[b, t, j] * relu(q[b, j, t] . k[b, s]) for
    s <= t, -1e30 above the diagonal; float32 (B, S, S).  ``q``
    (B, Hi, S, Di) and ``k`` (B, S, Di) go to the MXU in their own
    dtype and accumulate in float32; ``w`` (B, S, Hi) carries whatever
    scale the scores take.  Differentiable in all three."""
    if q.dtype != k.dtype:
        dt = jnp.promote_types(q.dtype, k.dtype)
        q, k = q.astype(dt), k.astype(dt)
    return _index_scores(q, k, w)


def index_scores_ref(q, k, w):
    """XLA oracle of ``index_scores`` (holds (B, Hi, S, S))."""
    s = q.shape[2]
    sc = jnp.einsum("bjtd,bsd->bjts", q, k,
                    preferred_element_type=jnp.float32,
                    precision=matmul_precision(q.dtype))
    out = jnp.einsum("bjts,btj->bts", jnp.maximum(sc, 0.0),
                     w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), out, _NEG)


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

_SELECT_ROWS = 128          # query rows whose scores stay in VMEM


def _ordered_bits(x):
    """float32 -> int32 whose (signed) order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _largest(count_below, bits: int, rows: int, start):
    """Per row, the largest ``v = start + (a sum of distinct powers of
    two under 2**bits)`` for which ``count_below(v)`` holds, found bit
    by bit from the top: ``bits`` passes, each a count over the row.
    int32 arithmetic wraps, so ``start`` = the smallest int32 walks the
    whole signed range."""
    def step(i, v):
        cand = v + jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count_below(cand), cand, v)
    return jax.lax.fori_loop(0, bits, step,
                             jnp.full((rows, 1), start, jnp.int32))


def _select_kernel(topk, col_bits, sc_ref, o_ref, key_scr):
    rows, s = key_scr.shape
    key_scr[...] = _ordered_bits(sc_ref[0])

    def count(mask):
        return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)

    # the k-th largest value of each row: the largest that at least
    # topk entries reach
    thr = _largest(lambda c: count(key_scr[...] >= c) >= topk, 32, rows,
                   jnp.iinfo(jnp.int32).min)
    key = key_scr[...]
    above, at = key > thr, key == thr
    # of the entries exactly AT it only as many as still fit, the lower
    # index first: the last column that may still take one, by the same
    # bisection over the column's bits
    need = topk - count(above)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
    last = _largest(lambda c: count(at & (col < c)) < need, col_bits, rows,
                    0)
    keep = (above | (at & (col <= last))) & (sc_ref[0] > 0.5 * _NEG)
    o_ref[0] = keep.astype(jnp.int32).astype(jnp.int8)


@jax.named_scope("apex_sparse_attn/select")
def select_topk(scores, topk: int):
    """``scores`` (B, S, S) float32 with -1e30 above the diagonal (what
    ``index_scores`` returns) -> int8 (B, S, S), 1 where key s is among
    the ``min(topk, t + 1)`` best-scored keys s <= t of query t: the set
    ``lax.top_k`` returns, exactly, equal scores going to the lower
    index.  No sort: a Pallas kernel keeps a block of rows' scores in
    VMEM as their order-preserving integer image and finds each row's
    k-th largest value bit by bit (32 passes that count), then, among
    the entries equal to it, the last column that still fits (as many
    passes as a column index has bits).  Always the same work, tie or
    none.  ``topk >= S`` keeps every causal pair and reads no score.
    Not differentiated."""
    scores = jax.lax.stop_gradient(scores).astype(jnp.float32)
    b, s, _ = scores.shape
    if topk >= s:
        return (scores > 0.5 * _NEG).astype(jnp.int8)
    sp = _round_up(s, _LANES)
    rows = min(_SELECT_ROWS, sp)
    sq = _round_up(s, rows)
    padded = jnp.pad(scores, ((0, 0), (0, sq - s), (0, sp - s)),
                     constant_values=_NEG)
    out = pl.pallas_call(
        functools.partial(_select_kernel, topk, max(sp - 1, 1).bit_length()),
        grid=(b, sq // rows),
        in_specs=[pl.BlockSpec((1, rows, sp), lambda b, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, rows, sp), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, sp), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, sp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="apex_index_select",
    )(padded)
    return out[:, :s, :s]


def select_topk_ref(scores, topk: int):
    """``lax.top_k``'s set as a mask (the oracle; sorts)."""
    b, s, _ = scores.shape
    _, idx = jax.lax.top_k(scores, min(topk, s))
    rows = jnp.arange(s)[None, :, None]
    mask = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], rows, idx].set(True)
    return (mask & (scores > 0.5 * _NEG)).astype(jnp.int8)


# ---------------------------------------------------------------------------
# the indexer's objective
# ---------------------------------------------------------------------------

def _loss_kernel(scale, h, blk, q_ref, k_ref, lse_ref, sel_ref, sc_ref,
                 lsei_ref, kl_ref, g_ref, p_scr):
    j, kk, n = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    active = kk <= _last_kv(j, blk, blk)

    @pl.when((kk == 0) & (n == 0))
    def _init_row():
        kl_ref[...] = jnp.zeros_like(kl_ref)

    @pl.when(active & (n == 0))
    def _init_tile():
        p_scr[...] = jnp.zeros_like(p_scr)

    @pl.when(active)
    def _head():
        sc = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        p = jnp.exp(sc - lse_ref[0, :, :1])
        p_scr[...] += jnp.where(sel_ref[0].astype(jnp.int32) != 0, p, 0.0)

    @pl.when(active & (n == h - 1))
    def _fold():
        p = p_scr[...] * (1.0 / h)
        sel = sel_ref[0].astype(jnp.int32) != 0
        logq = sc_ref[0] - lsei_ref[0, :, :1]
        some = sel & (p > 0.0)
        kl = jnp.where(some, p * (jnp.log(jnp.where(some, p, 1.0)) - logq),
                       0.0)
        kl_ref[0] += jnp.broadcast_to(
            jnp.sum(kl, axis=1, keepdims=True), kl_ref.shape[1:])
        g_ref[0] = jnp.where(sel, jnp.exp(jnp.where(sel, logq, 0.0)) - p,
                             0.0)


def _lanes(x, sp):
    """(rows, S) -> (rows, SP, 128), the layout a kernel reads a
    per-row scalar in without a transpose."""
    x = _pad_to(x, 1, sp)
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


def _selected_lse(scores, key_mask):
    return jax.nn.logsumexp(
        jnp.where(key_mask != 0, scores, -jnp.inf), axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _index_loss(scores, key_mask, q, k, lse, scale):
    return _index_loss_fwd(scores, key_mask, q, k, lse, scale)[0]


def _index_loss_fwd(scores, key_mask, q, k, lse, scale):
    b, h, s, d = q.shape
    hk = k.shape[1]
    blk, sp, n = _tiles(s)
    dp = _round_up(d, _LANES)
    q3 = _pad_to(_pad_to(q, 2, sp), 3, dp).reshape(b * h, sp, dp)
    k3 = _pad_to(_pad_to(k, 2, sp), 3, dp).reshape(b * hk, sp, dp)
    sel = _pad_to(_pad_to((key_mask != 0).astype(jnp.int8), 1, sp), 2, sp)
    sc = _pad_to(_pad_to(scores.astype(jnp.float32), 1, sp), 2, sp)

    def tile(b, j, kk, n):
        return (b, j, jnp.minimum(kk, _last_kv(j, blk, blk)))

    def q_row(b, j, kk, n):
        return (b * h + n, j, 0)

    kl, g = pl.pallas_call(
        functools.partial(_loss_kernel, scale, h, blk),
        grid=(b, n, n, h),
        in_specs=[
            pl.BlockSpec((1, blk, dp), q_row),
            pl.BlockSpec((1, blk, dp), lambda b, j, kk, n: (
                _kv_row(b * h + n, h, hk), tile(b, j, kk, n)[2], 0)),
            pl.BlockSpec((1, blk, _LANES), q_row),
            pl.BlockSpec((1, blk, blk), tile),
            pl.BlockSpec((1, blk, blk), tile),
            pl.BlockSpec((1, blk, _LANES), lambda b, j, kk, n: (b, j, 0))],
        out_specs=[
            pl.BlockSpec((1, blk, _LANES), lambda b, j, kk, n: (b, j, 0)),
            pl.BlockSpec((1, blk, blk), tile)],
        out_shape=[jax.ShapeDtypeStruct((b, sp, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, sp, sp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret_mode(),
        name="apex_index_loss",
    )(q3, k3, _lanes(lse.reshape(b * h, s), sp), sel, sc,
      _lanes(_selected_lse(scores, key_mask), sp))
    loss = jnp.sum(kl[:, :s, 0]) / (b * s)
    return loss, g[:, :s, :s]


def _index_loss_bwd(scale, g_scores, ct):
    b, s, _ = g_scores.shape
    return g_scores * (ct / (b * s)), None, None, None, None


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def _loss_operands(scores, key_mask, q, k, lse, scale):
    """``_index_loss``'s arguments: the main attention detached (it is
    the target, not a participant), q and k in one dtype."""
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    if q.dtype != k.dtype:
        dt = jnp.promote_types(q.dtype, k.dtype)
        q, k = q.astype(dt), k.astype(dt)
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    return scores, key_mask, q, k, lse, sc


@jax.named_scope("apex_sparse_attn/index_loss")
def index_loss(scores, key_mask, q, k, lse, scale=None):
    """The indexer's objective: the mean over queries of
    KL(p_t || softmax_{S_t}(I_t)), where p_t is the main attention's
    distribution over the selected keys averaged over its heads.

    ``scores`` (B, S, S) from ``index_scores``, ``key_mask`` (B, S, S)
    from ``select_topk``, ``q`` (B, H, S, D) / ``k`` (B, HK, S, D) the
    main attention's operands and ``lse`` (B, H, S) what
    ``flash_attention(key_mask=..., return_lse=True)`` returned for
    them.  Differentiable in ``scores`` alone: the main attention is
    the target, not a participant."""
    return _index_loss(*_loss_operands(scores, key_mask, q, k, lse, scale))


@jax.named_scope("apex_sparse_attn/index_loss")
def index_loss_and_grad(scores, key_mask, q, k, lse, scale=None):
    """``index_loss``'s value AND ``g`` (B, S, S) float32, both from its
    one kernel pass: ``g = softmax_{S_t}(I_t) - p_t`` on the selected
    keys and 0 elsewhere, the gradient with respect to ``scores`` of
    the SUM of the rows' divergences.  The loss is their mean, so
    ``dL_I/dI = g / (B * S)``: that factor is the caller's to apply, to
    whatever small thing it pulls ``g`` back to rather than to a
    (B, S, S) array.  Neither result is differentiable."""
    return _index_loss_fwd(*_loss_operands(
        jax.lax.stop_gradient(scores), key_mask, q, k, lse, scale))


def index_loss_ref(scores, key_mask, q, k, scale=None):
    """XLA oracle of ``index_loss`` (holds (B, H, S, S); computes the
    main attention's probabilities itself)."""
    sc = scale if scale is not None else _default_scale(q.shape[-1])
    rep = q.shape[1] // k.shape[1]
    sel = (key_mask != 0)[:, None]
    main = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, rep, axis=1),
                      preferred_element_type=jnp.float32,
                      precision=matmul_precision(q.dtype)) * sc
    p = jnp.mean(jax.nn.softmax(jnp.where(sel, main, -jnp.inf), axis=-1),
                 axis=1)
    p = jax.lax.stop_gradient(p)
    logq = jax.nn.log_softmax(
        jnp.where(sel[:, 0], scores, -jnp.inf), axis=-1)
    some = sel[:, 0] & (p > 0)
    kl = jnp.where(some, p * (jnp.log(jnp.where(some, p, 1.0))
                              - jnp.where(some, logq, 0.0)), 0.0)
    return jnp.mean(jnp.sum(kl, axis=-1))
