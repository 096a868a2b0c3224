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

Everything but the last three lines runs in parallel over chunks, as
XLA code (``_prep``); the three run in order, chunk after chunk, in ONE
Pallas kernel that keeps S in VMEM (``apex_kda_fwd``) and writes the
state each chunk starts from.  The backward is one Pallas kernel over
the chunks in reverse (``apex_kda_bwd``): it reads those states,
recomputes U, carries dS in VMEM and writes the cotangents of
``_prep``'s outputs, which ``jax.vjp`` of ``_prep`` takes to q, k, v, g
and beta.  Everything is float32: the state, the parallel part and the
kernels' products (``Precision.HIGHEST``).

No ``exp`` overflows: g <= 0, so a decay between two positions is at
most 1 when it is taken from the earlier to the later.  A product
across sub-chunks of ``SUB`` rows is split at the last row before the
later sub-chunk (both factors at most 1); inside a sub-chunk each
pair's product is taken from its own exponent differences, which are
non-positive wherever they are used.  At the published initialisation
a step's log-decay reaches -1.6, so a decay taken from the chunk's
start would overflow float32 within 64 steps.

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

from apex_tpu.ops._dispatch import interpret_mode

CHUNK = 64          # positions a chunk: the state is written once a chunk
SUB = 16            # rows a sub-chunk of the intra-chunk products
HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
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
        xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (kk - 1, 0), (0, 0)))
        y = sum(w[j].astype(jnp.float32) * xp[:, j:j + s]
                for j in range(kk))
        return jax.nn.silu(y).astype(x.dtype)


def l2_normalize(x, eps: float = 1e-6):
    """x / ||x|| over the last axis, float32 (FLA's ``l2norm``)."""
    with jax.named_scope("apex_kda/norm"):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_recurrent_ref(q, k, v, g, beta, scale=None):
    """The recurrence token by token (``lax.scan``), float32 at
    ``HIGHEST``: the oracle of ``chunk_kda``."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 0)  # noqa: E731
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

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                        (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)


# ---------------------------------------------------------------------------
# inside a chunk, in parallel over chunks (XLA)
# ---------------------------------------------------------------------------

def _intra(x, k, G, strict):
    """(..., C, C): X[r, i] = sum_c x[r, c] k[i, c] exp(G[r, c] - G[i, c])
    for i < r (``strict``) or i <= r, zeros above; x, k, G (..., C, d)."""
    c, d = x.shape[-2:]
    ns = c // SUB
    lead = x.shape[:-2]
    xs, ks, gs = (t.reshape(lead + (ns, SUB, d)) for t in (x, k, G))
    rows, cols = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]

    # a sub-chunk's own block from every pair's own exponent differences
    # (SUB x SUB x d a sub-chunk, 16 times the size of q); rematerialised,
    # so that the backward pass keeps none of them
    @jax.checkpoint
    def own_block(xs, ks, gs):
        ok = (rows > cols if strict else rows >= cols)[..., None]
        diff = gs[..., :, None, :] - gs[..., None, :, :]
        e = jnp.where(ok, jnp.exp(jnp.where(ok, diff, 0.0)), 0.0)
        return jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * e, -1)

    diag = own_block(xs, ks, gs)                    # (..., ns, SUB, SUB)
    # earlier sub-chunks: sub-chunk a's rows against every row i before
    # it, split at row a*SUB - 1 (G there is ``ref``; row -1's G is 0)
    ref = jnp.concatenate([jnp.zeros_like(gs[..., :1, -1:, :]),
                           gs[..., :-1, -1:, :]], axis=-3)  # (.., ns, 1, d)
    before = (jnp.arange(c)[None, :] < SUB * jnp.arange(ns)[:, None])[
        :, :, None]                                         # (ns, C, 1)
    left = xs * jnp.exp(gs - ref)
    right = jnp.where(before, k[..., None, :, :] * jnp.exp(
        jnp.where(before, ref - G[..., None, :, :], 0.0)), 0.0)
    off = jnp.einsum("...ard,...aid->...ari", left, right,
                     precision=HIGHEST)                     # (.., ns, SUB, C)
    own = (jnp.arange(c) // SUB)[None, :] == jnp.arange(ns)[:, None, None]
    out = jnp.where(own, jnp.tile(diag, (1,) * (diag.ndim - 1) + (ns,)), off)
    return out.reshape(lead + (c, c))


def _prep(q, k, v, g, beta):
    """(..., C, d) chunks -> (q~, k^, W_k, W_v, M, gamma), float32: the
    kernels' operands, gamma (..., dk) the chunk's whole decay."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)
    eg = jnp.exp(G)
    a = _intra(k, k, G, strict=True) * beta[..., None]
    c = a.shape[-1]
    rhs = jnp.concatenate([k * eg, v], axis=-1) * beta[..., None]
    w = jax.lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    dk = k.shape[-1]
    last = G[..., -1:, :]
    return (q * eg, k * jnp.exp(last - G), w[..., :dk], w[..., dk:],
            _intra(q, k, G, strict=False), jnp.exp(last[..., 0, :]))


# ---------------------------------------------------------------------------
# across chunks, in order (Pallas: the state in VMEM)
# ---------------------------------------------------------------------------

def _fwd_kernel(scale, hb, qt_ref, kh_ref, wk_ref, wv_ref, m_ref, gam_ref,
                o_ref, st_ref, s_scr):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for j in range(hb):
        p = s_scr[j]                            # S^T (dv, dk) at the start
        st_ref[j] = p
        u = wv_ref[j] - _dot(wk_ref[j], p, ((1,), (1,)))
        o = _dot(qt_ref[j], p, ((1,), (1,))) + _dot(m_ref[j], u, ((1,), (0,)))
        o_ref[j] = o * scale
        s_scr[j] = (p * gam_ref[j, pl.ds(t, 1), :]
                    + _dot(u, kh_ref[j], ((0,), (0,))))


def _bwd_kernel(scale, hb, nc, qt_ref, kh_ref, wk_ref, wv_ref, m_ref,
                gam_ref, st_ref, do_ref, dqt_ref, dkh_ref, dwk_ref, dwv_ref,
                dm_ref, dgam_ref, dp_scr):
    n = nc - 1 - pl.program_id(1)               # chunks in reverse

    @pl.when(n == nc - 1)
    def _init():
        dp_scr[...] = jnp.zeros_like(dp_scr)

    for j in range(hb):
        p, dpn = st_ref[j], dp_scr[j]           # S^T at the start, dS^T at the end
        u = wv_ref[j] - _dot(wk_ref[j], p, ((1,), (1,)))
        do = do_ref[j] * scale
        du = (_dot(m_ref[j], do, ((0,), (0,)))
              + _dot(kh_ref[j], dpn, ((1,), (1,))))
        dqt_ref[j] = _dot(do, p, ((1,), (0,)))
        dwk_ref[j] = -_dot(du, p, ((1,), (0,)))
        dkh_ref[j] = _dot(u, dpn, ((1,), (0,)))
        dwv_ref[j] = du
        dm_ref[j] = _dot(do, u, ((1,), (1,)))
        dgam_ref[j, pl.ds(n, 1), :] = jnp.sum(dpn * p, axis=0, keepdims=True)
        dp_scr[j] = (_dot(do, qt_ref[j], ((0,), (0,)))
                     + dpn * gam_ref[j, pl.ds(n, 1), :]
                     - _dot(du, wk_ref[j], ((0,), (0,))))


def _heads_a_step(bh):
    """Heads one grid step carries, and one group of ``chunk_kda``'s
    map: their products are independent, so the compiler can overlap
    them; the group's float32 operands, cotangents and solve residuals
    are the scan's temporaries (0.48 GB for a layer's gradient at 4
    heads of 4096 positions, as compiled for a v5e)."""
    return next(n for n in (4, 2, 1) if bh % n == 0)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _scan_fwd(scale, qt, kh, wk, wv, m, gam):
    """(BH, N, C, d) operands -> (o (BH, N, C, dv), states (BH, N, dv,
    dk): S^T before each chunk)."""
    bh, nc, c, dk = qt.shape
    dv = wv.shape[-1]
    hb = _heads_a_step(bh)
    rows = lambda t: t.reshape(bh, nc * t.shape[2], t.shape[3])  # noqa: E731
    chunk = lambda w: pl.BlockSpec((hb, c, w),                   # noqa: E731
                                   lambda i, t: (i, t, 0))
    o, st = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, hb),
        grid=(bh // hb, nc),
        in_specs=[chunk(dk), chunk(dk), chunk(dk), chunk(dv), chunk(c),
                  pl.BlockSpec((hb, nc, dk), lambda i, t: (i, 0, 0))],
        out_specs=[chunk(dv),
                   pl.BlockSpec((hb, dv, dk), lambda i, t: (i, t, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, nc * c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((bh, nc * dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=_params(), interpret=interpret_mode(),
        name="apex_kda_fwd",
    )(rows(qt), rows(kh), rows(wk), rows(wv), rows(m), gam)
    return o.reshape(bh, nc, c, dv), st


def _scan_bwd(scale, qt, kh, wk, wv, m, gam, st, do):
    """The cotangents of ``_prep``'s outputs from the output's ``do``."""
    bh, nc, c, dk = qt.shape
    dv = wv.shape[-1]
    hb = _heads_a_step(bh)
    rows = lambda t: t.reshape(bh, nc * t.shape[2], t.shape[3])  # noqa: E731
    chunk = lambda w: pl.BlockSpec(                              # noqa: E731
        (hb, c, w), lambda i, t: (i, nc - 1 - t, 0))
    whole = pl.BlockSpec((hb, nc, dk), lambda i, t: (i, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, hb, nc),
        grid=(bh // hb, nc),
        in_specs=[chunk(dk), chunk(dk), chunk(dk), chunk(dv), chunk(c),
                  whole,
                  pl.BlockSpec((hb, dv, dk), lambda i, t: (i, nc - 1 - t, 0)),
                  chunk(dv)],
        out_specs=[chunk(dk), chunk(dk), chunk(dk), chunk(dv), chunk(c),
                   whole],
        out_shape=[jax.ShapeDtypeStruct((bh, nc * c, w), jnp.float32)
                   for w in (dk, dk, dk, dv, c)]
        + [jax.ShapeDtypeStruct((bh, nc, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=_params(), interpret=interpret_mode(),
        name="apex_kda_bwd",
    )(rows(qt), rows(kh), rows(wk), rows(wv), rows(m), gam, st, rows(do))
    return tuple(o.reshape(bh, nc, c, -1) for o in outs[:5]) + (outs[5],)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, scale):
    return _chunked_fwd(q, k, v, g, beta, scale)[0]


def _chunked_fwd(q, k, v, g, beta, scale):
    o, st = _scan_fwd(scale, *_prep(q, k, v, g, beta))
    return o, (q, k, v, g, beta, st)


def _chunked_bwd(scale, res, do):
    q, k, v, g, beta, st = res
    ops, pull = jax.vjp(_prep, q, k, v, g, beta)
    return pull(_scan_bwd(scale, *ops, st, do))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunk_kda(q, k, v, g, beta, scale=None):
    """The gated delta rule over (B, H, S, ·): q, k (…, dk) (normalise
    them first: ``l2_normalize``), v (…, dv), g (…, dk) the log-decay
    (<= 0), beta (B, H, S) -> o (B, H, S, dv) in v's dtype; ``scale``
    defaults to dk^-1/2.  A length that is not a multiple of ``CHUNK``
    is padded with beta = 0 and g = 0 (the state passes through)."""
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    with jax.named_scope("apex_kda/scan"):
        b, h, s, dk = q.shape
        dv = v.shape[-1]
        sp = -(-s // CHUNK) * CHUNK
        nc = sp // CHUNK

        hb = _heads_a_step(b * h)

        def chunks(x):
            x = jnp.pad(x, ((0, 0), (0, 0), (0, sp - s))
                        + ((0, 0),) * (x.ndim - 3))
            return x.reshape((b * h // hb, hb, nc, CHUNK) + x.shape[3:])

        # one group of heads at a time: the float32 operands, their
        # cotangents and the solve's residuals of all heads at once
        # would be 2 GB at 32 heads of 4096 positions
        o = jax.lax.map(lambda x: _chunked(*x, scale),
                        tuple(chunks(t) for t in (q, k, v, g, beta)))
        return o.reshape(b, h, sp, dv)[:, :, :s].astype(v.dtype)
