"""Kimi Delta Attention (KDA): the gated delta rule with one decay per
key channel, chunked (arXiv:2510.26692; the chunked form of Gated
DeltaNet, arXiv:2412.06464, with KDA's per-channel decay, as FLA's
``chunk_kda`` computes it).  Per head, with a state S (dk x dv):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

``chunk_kda`` computes it in chunks of ``CHUNK`` positions.  Inside a
chunk, with G the running sum of g from the chunk's start and S_0 the
state before it, the WY / UT form gives every position at once:

    A[r, i] = beta_r k_r^T Diag(exp(G_r - G_i)) k_i      (i < r)
    M[r, i] = q_r^T Diag(exp(G_r - G_i)) k_i             (i <= r)
    (I + A) [W_k | W_v] = [beta * exp(G) k | beta * v]   (forward solve)
    U   = W_v - W_k S_0                                  (the new values)
    O   = scale (exp(G) q S_0 + M U)
    S_C = Diag(exp G_C) S_0 + (exp(G_C - G) k)^T U

XLA takes the running sum G (and, backward, its transpose); everything
else runs chunk after chunk in ONE Pallas kernel a direction, the state
in VMEM, four heads' chunk a grid step: two pairs, each pair one above
the other in the chunk's matrices (two heads fill the MXU's 128 x 128
tile that one head's 64 x 64 products and solve would use a quarter
of), the pairs two independent chains of products the scheduler
overlaps.  ``apex_kda_fwd``
computes the chunk's products, its solve and the lines above and writes
o, the state each chunk starts from, and M with T = (I + A)^-1 above
it; ``apex_kda_bwd`` runs over the chunks in reverse, reads those,
carries dS in VMEM and takes the pullback through the state update, the
solve (dRHS = T^T dW, dA = -tril_-1(dRHS W^T)) and the products to dq,
dk, dv, dG and dbeta (beta's share read off the product that carries
dk's, so A is not rebuilt).  Everything is float32: the state, the
operands and the kernels' products (``Precision.HIGHEST``).
Each kernel is lowered once a program (``_dispatch.lowered_once``).

The products and the inverse of (I + A) go by levels b = 1, 2, ...,
C/2, unrolled: a pair (r, i), i < r, belongs to the level of the
highest bit in which r and i differ, so its rows r lie in the upper
half and its columns i in the lower half of one block of 2b positions.
There the decay is split at m, the last row of the lower half:
exp(G_r - G_i) = exp(G_r - G_m) exp(G_m - G_i), both factors at most 1
(g <= 0), so no ``exp`` overflows: at the published initialisation a
step's log-decay reaches -1.6, and a decay taken from the chunk's start
would overflow float32 within 64 steps.  The inverse: on the diagonal
blocks of ``NEUMANN`` = 16, where A is nilpotent, the product
(I - A)(I + A^2)(I + A^4)(I + A^8); above them each level's block of A
doubles the blocks, T <- T - T A_b T (blocked forward substitution).
Both are as accurate as row-by-row substitution.

Also here: ``short_conv`` (the depthwise causal convolution and SiLU
in front of q, k and v), ``l2_normalize`` and ``kda_recurrent_ref``,
the per-token recurrence.  Scopes: ``apex_kda/conv``,
``apex_kda/norm``, ``apex_kda/scan`` (the gate's is the model's).
Shapes: (B, H, S, D); g (B, H, S, dk) float32, beta (B, H, S).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import lowered_once

CHUNK = 64          # positions a chunk: the state is written once a chunk
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32,
                               precision=HIGHEST)


# ---------------------------------------------------------------------------
# the parts around the scan
# ---------------------------------------------------------------------------

def short_conv(x, w):
    """SiLU of a depthwise causal convolution over time, no bias:
    x (B, S, C), w (K, C) -> (B, S, C) in x's dtype,
    y_t = silu(sum_j w_j x_{t - K + 1 + j}), float32 inside."""
    with jax.named_scope("apex_kda/conv"):
        kk, s = w.shape[0], x.shape[1]
        xp = jnp.pad(x.astype(F32), ((0, 0), (kk - 1, 0), (0, 0)))
        y = sum(w[j].astype(F32) * xp[:, j:j + s]
                for j in range(kk))
        return jax.nn.silu(y).astype(x.dtype)


def l2_normalize(x, eps: float = 1e-6):
    """x / ||x|| over the last axis, float32 (FLA's ``l2norm``)."""
    with jax.named_scope("apex_kda/norm"):
        x = x.astype(F32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_recurrent_ref(q, k, v, g, beta, scale=None):
    """The recurrence token by token (``lax.scan``), float32 at
    ``HIGHEST``: the oracle of ``chunk_kda``."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    f32 = lambda x: jnp.moveaxis(x.astype(F32), 2, 0)  # noqa: E731
    b, h, _, dk = q.shape
    dv = v.shape[-1]

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        kv = jnp.einsum("bhk,bhkv->bhv", kt, s, precision=HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", bt[..., None] * kt, vt - kv,
                           precision=HIGHEST)
        return s, scale * jnp.einsum("bhkv,bhk->bhv", s, qt,
                                     precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), F32),
                        (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)


# ---------------------------------------------------------------------------
# inside a chunk, in the kernels: the levels of the products and the solve
# ---------------------------------------------------------------------------

def _at_split(x, b):
    """x (c, d) with each row replaced by row m of its block of 2b rows,
    m the last row of the block's lower half (static slices and
    broadcasts, in groups of at least 8 rows)."""
    c, d = x.shape
    w = max(2 * b, 8)
    xs = x.reshape(c // w, w, d)
    p = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    out = None
    for s in range(0, w, 2 * b):
        row = jnp.broadcast_to(xs[:, s + b - 1:s + b, :], xs.shape)
        out = row if out is None else jnp.where(p >= s, row, out)
    return out.reshape(c, d)


def _to_split(y, b):
    """The transpose of ``_at_split``: each block's rows of y summed into
    its row m, zeros elsewhere."""
    c, d = y.shape
    w = max(2 * b, 8)
    ys = y.reshape(c // w, w, d)
    p = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    out = jnp.zeros_like(ys)
    for s in range(0, w, 2 * b):
        total = jnp.sum(ys[:, s:s + 2 * b, :], axis=1, keepdims=True)
        out = jnp.where(p == s + b - 1, total, out)
    return out.reshape(c, d)


class _Level:
    """Level b of the n rows of a grid step's chunks (its heads one
    above the other; 2b divides the chunk, so no block straddles two
    heads): ``off`` (n, n) its pairs, ``up`` (n, 1) the rows in an upper
    half, and the two factors of the split decay, ``el`` for rows and
    ``er`` for columns, each at most 1 (1 outside its half)."""

    def __init__(self, b, G):
        n = G.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        x = jnp.bitwise_xor(r, i)
        self.off = (r > i) & (x >= b) & (x < 2 * b)
        self.up = jnp.bitwise_and(
            jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0), b) != 0
        gm = _at_split(G, b)                                # G at m
        self.el = jnp.exp(jnp.where(self.up, G - gm, 0.0))
        self.er = jnp.exp(jnp.where(self.up, 0.0, gm - G))


# b = 1, 2, ..., CHUNK / 2, unrolled: the scheduler overlaps the levels'
# independent products
LEVELS = [1 << lv for lv in range(CHUNK.bit_length() - 1)]
# the inverse's diagonal blocks of this size by a Neumann product (as
# accurate as substitution there: 1e-7 of the float64 inverse, where the
# product over the whole chunk of 64 reads 2e-5)
NEUMANN = 16


def _products(q, k, G, beta):
    """M and T = (I + A)^-1 (A = beta A_raw), each (n, n) and
    block-diagonal, of a grid step's chunks: q, k, G (n, dk), beta
    (n, 1).  A level's rows of M and of A_raw are one product."""
    n = q.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    m = jnp.where(eye, jnp.sum(q * k, axis=-1, keepdims=True), 0.0)
    pa = {}
    for b in LEVELS:
        lvl = _Level(b, G)
        p = _dot(jnp.concatenate([q * lvl.el, k * lvl.el], axis=0),
                 k * lvl.er, ((1,), (1,)))
        m = m + jnp.where(lvl.off, p[:n], 0.0)
        pa[b] = jnp.where(lvl.off, beta * p[n:], 0.0)
    # blocks of NEUMANN: A there is nilpotent, so (I + A)^-1 =
    # (I - A)(I + A^2)(I + A^4)(I + A^8), a chain of four products
    a = sum(pa[b] for b in LEVELS if b < NEUMANN)
    t, p = eye.astype(F32) - a, a
    for _ in range(NEUMANN.bit_length() - 2):
        p = _dot(p, p, ((1,), (0,)))
        t = t + _dot(t, p, ((1,), (0,)))
    for b in LEVELS:                  # the levels above: T <- T - T A_b T
        if b >= NEUMANN:
            t = t - _dot(_dot(t, pa[b], ((1,), (0,))), t, ((1,), (0,)))
    return m, t


def _products_bwd(q, k, G, beta, dm, dA, dq, dk, dG):
    """The pullback of ``_products``' M and A = beta A_raw (cotangents
    ``dm``, ``dA``) added to dq, dk, dG, and sum_i dA[r, i] A_raw[r, i]
    (n, 1), beta's share, read off the product that carries dk (A_raw
    itself is not rebuilt): two products a level."""
    n = q.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    dd = jnp.sum(jnp.where(eye, dm, 0.0), axis=-1, keepdims=True)
    dq, dk = dq + dd * k, dk + dd * q
    db = jnp.zeros_like(beta)
    for b in LEVELS:
        lvl = _Level(b, G)
        kr, qe, ke = k * lvl.er, q * lvl.el, k * lvl.el
        d = jnp.concatenate([jnp.where(lvl.off, dm, 0.0),
                             jnp.where(lvl.off, dA, 0.0)], axis=0)
        y = _dot(d, kr, ((1,), (0,)))                       # rows: upper
        dkr = _dot(d, jnp.concatenate([qe, beta * ke], axis=0),
                   ((0,), (0,)))                            # rows: lower
        dql, dkl = y[:n], beta * y[n:]
        db = db + jnp.sum(y[n:] * ke, axis=-1, keepdims=True)
        d_el = (dql * q + dkl * k) * lvl.el                 # d(G_r - G_m)
        d_er = dkr * kr                                     # d(G_m - G_i)
        dq = dq + dql * lvl.el
        dk = dk + dkl * lvl.el + dkr * lvl.er
        dG = dG + d_el - d_er + _to_split(d_er - d_el, b)
    return dq, dk, dG, db


# ---------------------------------------------------------------------------
# across chunks, in order (Pallas: the state in VMEM)
# ---------------------------------------------------------------------------

def _rows(ref, g):
    """Heads ``g`` (a slice) of a (heads, c, w) block as (hs * c, w),
    float32: the group's heads one above the other."""
    x = ref[g].astype(F32)
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _put(ref, g, x):
    """The inverse of ``_rows``: (hs * c, w) rows to heads ``g``."""
    ref[g] = x.astype(ref.dtype).reshape((g.stop - g.start,)
                                         + ref.shape[1:])


def _grid(n, c):
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return r, i, (r // c) == (i // c)


def _solved(k, v, G, beta, t, hs):
    """exp(G), each head's G_C on its rows (n, dk) and the forward
    solve's W = [W_k | W_v] (one product)."""
    n, dk = G.shape
    c = n // hs
    eg = jnp.exp(G)
    g3 = G.reshape(hs, c, dk)
    gl = jnp.broadcast_to(g3[:, c - 1:c, :], g3.shape).reshape(n, dk)
    w = _dot(t, jnp.concatenate([beta * k * eg, beta * v], axis=1),
             ((1,), (0,)))
    return eg, gl, w


def _fwd_kernel(scale, stack, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref,
                st_ref, mt_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h0 in range(0, q_ref.shape[0], stack):
        _fwd_group(scale, h0, stack, q_ref, k_ref, v_ref, g_ref, b_ref,
                   o_ref, st_ref, mt_ref, s_scr)


def _fwd_group(scale, h0, hs, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref,
               st_ref, mt_ref, s_scr):
    """Heads h0 .. h0 + hs - 1, stacked block-diagonally."""
    c = q_ref.shape[1]
    g = slice(h0, h0 + hs)
    q, k, v, G, beta = (_rows(x, g) for x in (q_ref, k_ref, v_ref, g_ref,
                                              b_ref))
    m, t = _products(q, k, G, beta)
    r, i, _ = _grid(hs * c, c)
    # M, and T^T above it: each head's block of the block-diagonal pair
    mt = jnp.where(r >= i, m, t.T)
    for h in range(hs):
        mt_ref[h0 + h] = mt[h * c:(h + 1) * c, h * c:(h + 1) * c]
    eg, gl, w = _solved(k, v, G, beta, t, hs)
    wk, wv = w[:, :q.shape[1]], w[:, q.shape[1]:]
    kh, qt = k * jnp.exp(gl - G), q * eg
    heads = [slice(h * c, (h + 1) * c) for h in range(hs)]
    ps = [s_scr[h0 + h] for h in range(hs)]     # S^T (dv, dk) at the start
    us = [wv[x] - _dot(wk[x], p, ((1,), (1,))) for x, p in zip(heads, ps)]
    mu = _dot(m, jnp.concatenate(us, axis=0), ((1,), (0,)))
    for h, (x, p, u) in enumerate(zip(heads, ps, us)):
        st_ref[h0 + h] = p
        o = _dot(qt[x], p, ((1,), (1,))) + mu[x]
        o_ref[h0 + h] = (o * scale).astype(o_ref.dtype)
        s_scr[h0 + h] = (p * jnp.exp(gl[x][:1])
                         + _dot(u, kh[x], ((0,), (0,))))


def _bwd_kernel(scale, stack, q_ref, k_ref, v_ref, g_ref, b_ref, st_ref,
                mt_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dp_scr):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dp_scr[...] = jnp.zeros_like(dp_scr)

    for h0 in range(0, q_ref.shape[0], stack):
        _bwd_group(scale, h0, stack, q_ref, k_ref, v_ref, g_ref, b_ref,
                   st_ref, mt_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                   db_ref, dp_scr)


def _bwd_group(scale, h0, hs, q_ref, k_ref, v_ref, g_ref, b_ref, st_ref,
               mt_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
               dp_scr):
    c = q_ref.shape[1]
    n = hs * c
    g = slice(h0, h0 + hs)
    q, k, v, G, beta = (_rows(x, g) for x in (q_ref, k_ref, v_ref, g_ref,
                                              b_ref))
    r, i, same = _grid(n, c)
    mt = jnp.where(same, jnp.concatenate([_rows(mt_ref, g)] * hs, axis=1),
                   0.0)
    m = jnp.where(r >= i, mt, 0.0)
    t = jnp.where(r > i, mt.T, 0.0) + (r == i).astype(F32)
    eg, gl, w = _solved(k, v, G, beta, t, hs)
    dk = q.shape[1]
    wk, wv = w[:, :dk], w[:, dk:]
    kd = jnp.exp(gl - G)
    qt, kh = q * eg, k * kd
    do = _rows(do_ref, g) * scale
    heads = [slice(h * c, (h + 1) * c) for h in range(hs)]
    ps = [st_ref[h0 + h] for h in range(hs)]    # S^T at the start
    dps = [dp_scr[h0 + h] for h in range(hs)]   # dS^T at the end
    us = [wv[x] - _dot(wk[x], p, ((1,), (1,))) for x, p in zip(heads, ps)]
    u = jnp.concatenate(us, axis=0)
    # the state update
    du = _dot(m, do, ((0,), (0,))) + jnp.concatenate(
        [_dot(kh[x], dp, ((1,), (1,))) for x, dp in zip(heads, dps)], axis=0)
    dqt = jnp.concatenate([_dot(do[x], p, ((1,), (0,)))
                           for x, p in zip(heads, ps)], axis=0)
    dwk = -jnp.concatenate([_dot(du[x], p, ((1,), (0,)))
                            for x, p in zip(heads, ps)], axis=0)
    dkh = jnp.concatenate([_dot(ux, dp, ((1,), (0,)))
                           for ux, dp in zip(us, dps)], axis=0)
    dm = _dot(do, u, ((1,), (1,)))              # off its heads' blocks: unread
    dgl = []
    for h, (x, p, dp) in enumerate(zip(heads, ps, dps)):
        gam = jnp.exp(gl[x][:1])
        dgl.append(jnp.broadcast_to(
            jnp.sum(dkh[x] * kh[x], axis=0, keepdims=True)
            + jnp.sum(dp * p, axis=0, keepdims=True) * gam, (c, gl.shape[1])))
        dp_scr[h0 + h] = (_dot(do[x], qt[x], ((0,), (0,))) + dp * gam
                          - _dot(du[x], wk[x], ((0,), (0,))))
    # the solve: dRHS = T^T dW, dA = -tril_-1(dRHS W^T)
    drhs = _dot(t, jnp.concatenate([dwk, du], axis=1), ((0,), (0,)))
    da = -jnp.where((r > i) & same, _dot(drhs, w, ((1,), (1,))), 0.0)
    drk, drv = drhs[:, :dk], drhs[:, dk:]
    rk = beta * k * eg
    _put(dv_ref, g, beta * drv)
    # the decays of q~, k^ and the right-hand side
    last = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) % c == c - 1
    dG = (drk * rk + dqt * qt - dkh * kh
          + jnp.where(last, jnp.concatenate(dgl, axis=0), 0.0))
    dq = dqt * eg
    dk = beta * eg * drk + dkh * kd
    dq, dk, dG, db = _products_bwd(q, k, G, beta, dm, da, dq, dk, dG)
    _put(db_ref, g, db + jnp.sum(drk * k * eg, axis=-1, keepdims=True)
         + jnp.sum(drv * v, axis=-1, keepdims=True))
    _put(dq_ref, g, dq)
    _put(dk_ref, g, dk)
    _put(dg_ref, g, dG)


# heads stacked block-diagonally, and such stacks a grid step
STACK, GROUPS = 2, 2


def _heads_a_step(bh):
    """(STACK, heads a grid step): STACK heads one above the other in
    the chunk's matrices, GROUPS such stacks side by side (independent
    chains the scheduler can overlap); one head where bh does not
    divide."""
    hs = STACK * GROUPS
    return (STACK, hs) if bh % hs == 0 else (1, 1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _specs(hs, nc, c, widths, reverse):
    """``hs`` heads' chunk a grid step, in order or in reverse."""
    at = (lambda i, t: (i, nc - 1 - t, 0)) if reverse else \
        (lambda i, t: (i, t, 0))
    return [pl.BlockSpec((hs, c, w), at) for w in widths]


@lowered_once("apex_kda/scan", static_argnums=(0,))
def _scan_fwd(scale, q, k, v, G, beta, *, interpret):
    """(BH, S, d) operands, S a multiple of ``CHUNK``, G the running sum
    inside each chunk, beta (BH, S, 1) -> (o (BH, S, dv) in v's dtype,
    states (BH, N * dv, dk): S^T before each chunk, and for the backward
    each chunk's M with T^T above it (BH, S, CHUNK))."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    nc = s // CHUNK
    stack, hs = _heads_a_step(bh)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale, stack),
        grid=(bh // hs, nc),
        in_specs=_specs(hs, nc, CHUNK, (dk, dk, dv, dk, 1), False),
        out_specs=_specs(hs, nc, CHUNK, (dv,), False)
        + [pl.BlockSpec((hs, dv, dk), lambda i, t: (i, t, 0))]
        + _specs(hs, nc, CHUNK, (CHUNK,), False),
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, nc * dv, dk), F32),
                   jax.ShapeDtypeStruct((bh, s, CHUNK), F32)],
        scratch_shapes=[pltpu.VMEM((hs, dv, dk), F32)],
        compiler_params=_params(), interpret=interpret,
        name="apex_kda_fwd",
    )(q, k, v, G, beta)


@lowered_once("apex_kda/scan", static_argnums=(0,))
def _scan_bwd(scale, q, k, v, G, beta, st, mt, do, *, interpret):
    """dq, dk, dv (their operands' dtypes), dG and dbeta (float32) from
    the output's ``do``."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    nc = s // CHUNK
    stack, hs = _heads_a_step(bh)
    widths = (dk, dk, dv, dk, 1)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale, stack),
        grid=(bh // hs, nc),
        in_specs=_specs(hs, nc, CHUNK, widths, True)
        + [pl.BlockSpec((hs, dv, dk), lambda i, t: (i, nc - 1 - t, 0))]
        + _specs(hs, nc, CHUNK, (CHUNK, dv), True),
        out_specs=_specs(hs, nc, CHUNK, widths, True),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, G, beta)],
        scratch_shapes=[pltpu.VMEM((hs, dv, dk), F32)],
        compiler_params=_params(), interpret=interpret,
        name="apex_kda_bwd",
    )(q, k, v, G, beta, st, mt, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, G, beta, scale):
    return _scan_fwd(scale, q, k, v, G, beta)[0]


def _chunked_fwd(q, k, v, G, beta, scale):
    o, *saved = _scan_fwd(scale, q, k, v, G, beta)
    return o, (q, k, v, G, beta, *saved)


def _chunked_bwd(scale, res, do):
    return _scan_bwd(scale, *res, do)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunk_kda(q, k, v, g, beta, scale=None):
    """The gated delta rule over (B, H, S, ·): q, k (…, dk) (normalise
    them first: ``l2_normalize``), v (…, dv), g (…, dk) the log-decay
    (<= 0), beta (B, H, S) -> o (B, H, S, dv) in v's dtype; ``scale``
    defaults to dk^-1/2.  A length that is not a multiple of ``CHUNK``
    is padded with beta = 0 and g = 0 (the state passes through)."""
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    with jax.named_scope("apex_kda/scan"):
        b, h, s, _ = q.shape
        sp = -(-s // CHUNK) * CHUNK

        def rows(x):
            x = jnp.pad(x, ((0, 0), (0, 0), (0, sp - s))
                        + ((0, 0),) * (x.ndim - 3))
            return x.reshape((b * h, sp) + x.shape[3:])

        G = jnp.cumsum(rows(g.astype(F32)).reshape(b * h, sp // CHUNK,
                                                   CHUNK, -1), axis=2)
        o = _chunked(rows(q), rows(k), rows(v), G.reshape(b * h, sp, -1),
                     rows(beta.astype(F32))[..., None], scale)
        return o.reshape(b, h, sp, -1)[:, :, :s]
