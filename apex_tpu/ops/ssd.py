"""Mamba-2's selective state-space scan (SSD, "state-space duality",
arXiv:2405.21060), chunked.  Per head h of P channels, with B and C
shared by the heads of a group and a state S (P x N):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

(the D skip, the gate and the norm are the model's).  ``chunk_ssd``
computes it in chunks of ``CHUNK`` positions.  Inside a chunk, with L
the running sum of dt A from the chunk's start and S_0 the state before
it:

    M[t, s] = (C_t . B_s) exp(L_t - L_s) dt_s       (s <= t)
    y       = M x + exp(L) * (C S_0^T)
    S_Q     = exp(L_Q) S_0 + (x * exp(L_Q - L) dt)^T B

XLA takes the running sum L (and, backward, its transpose); everything
else runs chunk after chunk in ONE Pallas kernel a direction, the
state in VMEM.  A grid step is one group's chunk: C B^T (Q x Q) is
made once for the group's heads, and the heads go two at a time, side
by side on the lanes (two heads of 64 fill a 128-lane tile) and
block-diagonal in the products, so that a pair's output is one product
``[M_a | M_b | C e_a | C e_b] [x_a 0; 0 x_b; S_a^T 0; 0 S_b^T]`` and its
state update another.  ``apex_ssd_fwd`` writes y and the state each
chunk starts from; ``apex_ssd_bwd`` runs over the chunks in reverse,
reads those, carries dS in VMEM and takes the pullback to dx, dB, dC,
d dt and dL.  Everything inside is float32: the decays, the state and
the kernels' products (``Precision.HIGHEST``).  Each kernel is lowered
once a program (``_dispatch.lowered_once``).

No ``exp`` overflows: every exponent is a difference L_t - L_s with
s <= t or the running sum itself, both at most 0 (A < 0, dt > 0).

Also here: ``ssd_recurrent_ref``, the recurrence position by position.
Scope: ``apex_ssd/scan``.  Shapes: x (B, S, H, P), dt (B, S, H)
float32, A (H,), B and C (B, S, G, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import lowered_once

CHUNK = 128         # positions a chunk: the state is written once a chunk
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32,
                               precision=HIGHEST)


def ssd_recurrent_ref(x, dt, A, B, C):
    """The recurrence position by position (``lax.scan``), float32 at
    ``HIGHEST``: the oracle of ``chunk_ssd``."""
    b, _, nh, p = x.shape
    hg = nh // B.shape[2]
    heads = lambda t: jnp.repeat(t.astype(F32), hg, axis=2)  # noqa: E731
    tm = lambda t: jnp.moveaxis(t.astype(F32), 1, 0)  # noqa: E731

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (s * jnp.exp(dtt * A.astype(F32))[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((b, nh, p, B.shape[-1]), F32),
                        (tm(x), tm(dt), tm(heads(B)), tm(heads(C))))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)


# ---------------------------------------------------------------------------
# inside a grid step: one group's chunk
# ---------------------------------------------------------------------------

def _lanes(rows, q):
    """rows (r, Q), r <= Q -> (Q, Q) whose column k is row k: each
    head's L and dt along the sublanes (one transpose a grid step)."""
    pad = q - rows.shape[0]
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, q), F32)], axis=0)
    return rows.T


def _col(t, k):
    """Column k of t as (rows, 1) (a masked lane sum: exact)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t.shape[1]), 1)
    return jnp.sum(jnp.where(lane == k, t, 0.0), axis=1, keepdims=True)


def _total(v):
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


class _Head:
    """Head h of a grid step's group: its decays at the chunk's
    positions, M (Q, Q), C e (Q, N) and the state update's weights."""

    def __init__(self, h, hg, lr, dtr, cols, cb, cm, tri):
        q = lr.shape[1]
        self.lc = _col(cols, h)                             # L_t (Q, 1)
        self.dtc = _col(cols, hg + h)                       # dt_t
        self.lrow, self.dtrow = lr[h:h + 1], dtr[h:h + 1]   # (1, Q)
        self.last = self.lc[q - 1:q]                        # L_Q (1, 1)
        self.seg = jnp.exp(jnp.where(tri, self.lc - self.lrow, -jnp.inf))
        self.m = cb * self.seg * self.dtrow
        self.e = jnp.exp(self.lc)
        self.ce = cm * self.e
        self.ew = jnp.exp(self.last - self.lc)              # exp(L_Q - L_s)
        self.w = self.ew * self.dtc


def _stacked(k, pick, v):
    """v (rows, k P), the k heads' lanes side by side -> (k rows, k P),
    each head's rows holding its own lanes only: block-diagonal."""
    return jnp.concatenate([jnp.where(pick == i, v, 0.0) for i in range(k)],
                           axis=0)


def _by_lane(pick, values, shape):
    """Each head's value (broadcast) on its own lanes."""
    out = jnp.zeros(shape, F32)
    for i, v in enumerate(values):
        out = jnp.where(pick == i, v, out)
    return out


def _step_inputs(x_ref, dt_ref, l_ref, b_ref, c_ref):
    q = x_ref.shape[1]
    lr, dtr = l_ref[0], dt_ref[0]                           # (hg, Q)
    cols = _lanes(jnp.concatenate([lr, dtr], axis=0), q)
    bm, cm = b_ref[0].astype(F32), c_ref[0].astype(F32)     # (Q, N)
    cb = _dot(cm, bm, ((1,), (1,)))                         # C B^T
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return x_ref[0].astype(F32), lr, dtr, cols, bm, cm, cb, tri


def _fwd_kernel(hg, p, stack, x_ref, dt_ref, l_ref, b_ref, c_ref, y_ref,
                st_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    xg, lr, dtr, cols, bm, cm, cb, tri = _step_inputs(
        x_ref, dt_ref, l_ref, b_ref, c_ref)
    q = xg.shape[0]
    s_all = s_scr[...]                          # S^T (N, hg P) at the start
    st_ref[0] = s_all
    pick = jax.lax.broadcasted_iota(jnp.int32, (1, stack * p), 1) // p
    for j in range(0, hg, stack):
        lanes = slice(j * p, (j + stack) * p)
        hs = [_Head(j + i, hg, lr, dtr, cols, cb, cm, tri)
              for i in range(stack)]
        xp, sp = xg[:, lanes], s_all[:, lanes]
        lhs = jnp.concatenate([h.m for h in hs] + [h.ce for h in hs], axis=1)
        rhs = jnp.concatenate([_stacked(stack, pick, xp),
                               _stacked(stack, pick, sp)], axis=0)
        y_ref[0, :, lanes] = _dot(lhs, rhs, ((1,), (0,))).astype(y_ref.dtype)
        w = _by_lane(pick, [h.w for h in hs], (q, stack * p))
        decay = _by_lane(pick, [jnp.exp(h.last) for h in hs],
                         (1, stack * p))
        s_scr[:, lanes] = sp * decay + _dot(bm, xp * w, ((0,), (0,)))


def _bwd_kernel(hg, p, stack, x_ref, dt_ref, l_ref, b_ref, c_ref, st_ref,
                dy_ref, dx_ref, ddt_ref, dl_ref, db_ref, dc_ref, ds_scr):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    xg, lr, dtr, cols, bm, cm, cb, tri = _step_inputs(
        x_ref, dt_ref, l_ref, b_ref, c_ref)
    q, n = cb.shape[0], bm.shape[1]
    s_all, ds_all = st_ref[0], ds_scr[...]      # S^T at the start, dS^T at the end
    dyg = dy_ref[0].astype(F32)
    pick = jax.lax.broadcasted_iota(jnp.int32, (1, stack * p), 1) // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcb = jnp.zeros((q, q), F32)
    dc = db = jnp.zeros((q, n), F32)
    ct = jnp.zeros((q, q), F32)                 # column k: dL (k < hg), d dt
    rl, rdt = [], []                            # rows: dL, d dt
    for j in range(0, hg, stack):
        lanes = slice(j * p, (j + stack) * p)
        hs = [_Head(j + i, hg, lr, dtr, cols, cb, cm, tri)
              for i in range(stack)]
        xp, sp, dsp, dyp = (v[:, lanes] for v in (xg, s_all, ds_all, dyg))
        w = _by_lane(pick, [h.w for h in hs], (q, stack * p))
        decay = _by_lane(pick, [jnp.exp(h.last) for h in hs],
                         (1, stack * p))
        dybd = _stacked(stack, pick, dyp)                      # (k Q, k P)
        bds = _dot(bm, dsp, ((1,), (0,)))                   # B dS_Q^T
        mv = jnp.concatenate([h.m for h in hs], axis=0)
        dx_ref[0, :, lanes] = (_dot(mv, dybd, ((0,), (0,)))
                               + bds * w).astype(dx_ref.dtype)
        # dM and d(C e) of each head, stacked: (k Q, Q + N)
        g2 = _dot(dybd, jnp.concatenate([xp, sp], axis=0), ((1,), (1,)))
        cv = jnp.concatenate([h.ce for h in hs], axis=0)
        ds_scr[:, lanes] = _dot(cv, dybd, ((0,), (0,))) + dsp * decay
        db = db + _dot(xp * w, dsp, ((1,), (1,)))
        dxw, sds = xp * bds, sp * dsp
        for i, h in enumerate(hs):
            dm, dce = g2[i * q:(i + 1) * q, :q], g2[i * q:(i + 1) * q, q:]
            dc = dc + dce * h.e
            dw = jnp.sum(jnp.where(pick == i, dxw, 0.0), axis=1,
                         keepdims=True)                     # d w_s (Q, 1)
            dlast = (_total(dw * h.w)
                     + _total(jnp.where(pick == i, sds, 0.0))
                     * jnp.exp(h.last))
            h1 = dm * h.seg
            dcb = dcb + h1 * h.dtrow
            g0 = h1 * cb
            gg = g0 * h.dtrow
            cl = (jnp.sum(dce * h.ce, axis=1, keepdims=True) - dw * h.w
                  + jnp.sum(gg, axis=1, keepdims=True)
                  + jnp.where(at_last, dlast, 0.0))
            ct = jnp.where(lane == j + i, cl, ct)
            ct = jnp.where(lane == hg + j + i, dw * h.ew, ct)
            rl.append(-jnp.sum(gg, axis=0, keepdims=True))
            rdt.append(jnp.sum(g0, axis=0, keepdims=True))
    rows = ct.T
    dl_ref[0] = jnp.concatenate(rl, axis=0) + rows[:hg]
    ddt_ref[0] = jnp.concatenate(rdt, axis=0) + rows[hg:2 * hg]
    dc_ref[0] = (dc + _dot(dcb, bm, ((1,), (0,)))).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dcb, cm, ((0,), (0,)))).astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# across chunks, in order (Pallas: the state in VMEM)
# ---------------------------------------------------------------------------

def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _geometry(x, dt, groups):
    """(batch, positions, heads a group, head width, chunks, pair)."""
    b, s, width = x.shape
    hg = dt.shape[1]
    p = width // (hg * groups)
    return b, s, hg, p, s // CHUNK, 2 if hg % 2 == 0 else 1


def _specs(groups, nc, reverse, x_width, hg, n):
    """The BlockSpecs of x-like, dt-like and B-like operands: one
    group's chunk a grid step (``i`` = batch x groups + group)."""
    at = (lambda t: nc - 1 - t) if reverse else (lambda t: t)
    xs = pl.BlockSpec((1, CHUNK, x_width),
                      lambda i, t: (i // groups, at(t), i % groups))
    ds = pl.BlockSpec((1, hg, CHUNK), lambda i, t: (i, 0, at(t)))
    bs = pl.BlockSpec((1, CHUNK, n),
                      lambda i, t: (i // groups, at(t), i % groups))
    st = pl.BlockSpec((1, n, x_width), lambda i, t: (i, at(t), 0))
    return xs, ds, bs, st


@lowered_once("apex_ssd/scan", static_argnums=(0,))
def _scan_fwd(groups, x, dt, L, B, C, *, interpret):
    """x (b, S, H P), dt and L (b G, H / G, S) float32, B and C
    (b, S, G N), S a multiple of ``CHUNK`` -> (y (b, S, H P) in x's
    dtype, states (b G, chunks N, H / G P) float32: S^T before each
    chunk, for the backward)."""
    b, s, hg, p, nc, stack = _geometry(x, dt, groups)
    n = B.shape[-1] // groups
    xs, ds, bs, st = _specs(groups, nc, False, hg * p, hg, n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hg, p, stack),
        grid=(b * groups, nc),
        in_specs=[xs, ds, ds, bs, bs],
        out_specs=[xs, st],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b * groups, nc * n, hg * p), F32)],
        scratch_shapes=[pltpu.VMEM((n, hg * p), F32)],
        compiler_params=_params(), interpret=interpret,
        name="apex_ssd_fwd",
    )(x, dt, L, B, C)


@lowered_once("apex_ssd/scan", static_argnums=(0,))
def _scan_bwd(groups, x, dt, L, B, C, st, dy, *, interpret):
    """dx, d dt, dL, dB, dC (their operands' dtypes) from the output's
    ``dy``."""
    b, s, hg, p, nc, stack = _geometry(x, dt, groups)
    n = B.shape[-1] // groups
    xs, ds, bs, sts = _specs(groups, nc, True, hg * p, hg, n)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hg, p, stack),
        grid=(b * groups, nc),
        in_specs=[xs, ds, ds, bs, bs, sts, xs],
        out_specs=[xs, ds, ds, bs, bs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in (x, dt, L, B, C)],
        scratch_shapes=[pltpu.VMEM((n, hg * p), F32)],
        compiler_params=_params(), interpret=interpret,
        name="apex_ssd_bwd",
    )(x, dt, L, B, C, st, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(x, dt, L, B, C, groups):
    # the forward rule is traced under an empty abstract mesh, the
    # primal under none: the same context here makes the two one jit
    # cache entry, so a program lowers the forward kernel once
    with jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh((), ())):
        return _scan_fwd(groups, x, dt, L, B, C)[0]


def _chunked_fwd(x, dt, L, B, C, groups):
    y, st = _scan_fwd(groups, x, dt, L, B, C)
    return y, (x, dt, L, B, C, st)


def _chunked_bwd(groups, res, dy):
    return _scan_bwd(groups, *res, dy)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunk_ssd(x, dt, A, B, C):
    """The SSD scan: x (b, s, H, P), dt (b, s, H) the step (after the
    softplus, > 0), A (H,) (< 0), B and C (b, s, G, N) with H a multiple
    of G -> y (b, s, H, P) in x's dtype, without the D skip, in chunks
    of ``CHUNK``.  A length that is not a multiple of ``CHUNK`` is
    padded with dt = 0 (the state passes through)."""
    with jax.named_scope("apex_ssd/scan"):
        b, s, nh, p = x.shape
        g, n = B.shape[2:]
        sp = -(-s // CHUNK) * CHUNK

        def padded(v):
            return jnp.pad(v, ((0, 0), (0, sp - s)) + ((0, 0),) * (v.ndim - 2))

        dt = padded(dt.astype(F32))
        L = jnp.cumsum((dt * A.astype(F32)).reshape(b, sp // CHUNK, CHUNK, nh),
                       axis=2).reshape(b, sp, nh)

        def by_group(v):                # (b, sp, H) -> (b G, H / G, sp)
            return jnp.swapaxes(v, 1, 2).reshape(b * g, nh // g, sp)

        y = _chunked(padded(x).reshape(b, sp, nh * p), by_group(dt),
                     by_group(L), padded(B).reshape(b, sp, g * n),
                     padded(C).reshape(b, sp, g * n), g)
        return y.reshape(b, sp, nh, p)[:, :s]
