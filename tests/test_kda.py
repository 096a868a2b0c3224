"""Kimi Delta Attention's chunked scan (``ops/kda.py``) against the
token-by-token recurrence, forward and every input's gradient, at
lengths around the chunk of 64, at the published gate initialisation
and at decays ten times stronger, over several heads and batches, with
bfloat16 operands and with decays that would overflow a chunk-start
exponent; its two neighbours (the short convolution, the L2 norm)
against plain formulas.  CPU, interpreted Pallas; nothing is timed."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import kda


def _inputs(seed, s, strength, b=1, h=2, dk=32, dv=16):
    """q, k normalised; the log-decay as FLA initialises it
    (-exp(A_log) softplus(dt_bias), A_log = log U[1, 16], softplus in
    [0.001, 0.1] log-uniform) times ``strength``; beta a sigmoid."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (b, h, s, dk)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (b, h, s, dk)))
    v = jax.random.normal(ks[2], (b, h, s, dv))
    a = jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0)
    rate = jnp.exp(jax.random.uniform(ks[4], (b, h, s, dk),
                                      minval=math.log(1e-3),
                                      maxval=math.log(0.1)))
    g = -a[None, :, None, None] * rate * strength
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h, s)))
    return q, k, v, g, beta


@pytest.mark.parametrize("strength", [1.0, 10.0], ids=["published", "x10"])
@pytest.mark.parametrize("s", [1, 63, 64, 200])
def test_chunked_scan_agrees_with_the_recurrence(s, strength):
    args = _inputs(s, s, strength)
    got, want = kda.chunk_kda(*args), kda.kda_recurrent_ref(*args)
    assert got.shape == want.shape == (1, 2, s, 16)
    # float32 both ways at HIGHEST: only the order of sums differs
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    ct = jax.random.normal(jax.random.key(9), got.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * ct)

    gots = jax.grad(loss(kda.chunk_kda), argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(loss(kda.kda_recurrent_ref),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), gots, wants):
        assert bool(jnp.all(jnp.isfinite(g))), name
        # relative to the largest entry: the readings reach 1.05e-5 (g at
        # 200 positions and the stronger decays), 3e-5 leaves three times
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g / scale, w / scale, atol=3e-5, rtol=0,
                                   err_msg=name)


def _grads(fn, args, ct):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("b, h, s, dtype, log_decay, atol, gtol", [
    # two batches of three heads, a length that is not a chunk multiple:
    # six heads, not a multiple of four, one head a grid step
    (2, 3, 100, jnp.float32, None, 2e-6, 3e-5),
    # bfloat16 q, k, v, whose gradients come back in bfloat16; an odd
    # count of heads, one a grid step
    (1, 3, 130, jnp.bfloat16, None, 2e-2, 2e-2),
    # -20 a step: exp(G) of a chunk's later rows underflows to 0 and
    # exp(-G) would overflow; the split products never take either
    (1, 2, 128, jnp.float32, -20.0, 2e-6, 3e-5),
    # eight heads at a ragged length: two grid rows of two block-diagonal
    # pairs each
    (2, 4, 130, jnp.float32, None, 2e-6, 3e-5),
], ids=["ragged_six_heads", "bfloat16", "overflowing_decay", "two_pairs"])
def test_kernels_agree_with_the_recurrence_in_every_gradient(
        b, h, s, dtype, log_decay, atol, gtol):
    q, k, v, g, beta = _inputs(11 + s, s, 1.0, b=b, h=h)
    if log_decay is not None:
        g = jnp.full_like(g, log_decay)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    got = kda.chunk_kda(q, k, v, g, beta)
    assert got.shape == (b, h, s, 16) and got.dtype == dtype
    args32 = [t.astype(jnp.float32) for t in (q, k, v)] + [g, beta]
    want = kda.kda_recurrent_ref(*args32)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol,
                               rtol=0)
    ct = jax.random.normal(jax.random.key(5), got.shape)
    gots = _grads(kda.chunk_kda, (q, k, v, g, beta), ct)
    wants = _grads(kda.kda_recurrent_ref, args32, ct)
    for name, x, w, arg in zip("q k v g beta".split(), gots, wants,
                               (q, k, v, g, beta)):
        assert x.dtype == arg.dtype, name
        x = x.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(x))), name
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(x / scale, w / scale, atol=gtol, rtol=0,
                                   err_msg=name)


def test_strong_decays_do_not_overflow_inside_a_chunk():
    """A log-decay of -20 a step: a decay from the chunk's start would
    reach exp(1280); every factor the scan takes stays at most 1."""
    q, k, v, g, beta = _inputs(7, 128, 1.0)
    g = jnp.full_like(g, -20.0)
    got = kda.chunk_kda(q, k, v, g, beta)
    np.testing.assert_allclose(got, kda.kda_recurrent_ref(q, k, v, g, beta),
                               atol=2e-6, rtol=0)


def test_the_state_passes_through_padding_and_bfloat16_operands():
    q, k, v, g, beta = _inputs(3, 100, 1.0)
    bf = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    got = kda.chunk_kda(*bf, g, beta)
    assert got.dtype == jnp.bfloat16
    want = kda.kda_recurrent_ref(*(t.astype(jnp.float32) for t in bf), g,
                                 beta)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


def test_short_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.key(0), (2, 10, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    y = kda.short_conv(x, w)
    xp = np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))
    want = sum(np.asarray(w)[j] * xp[:, j:j + 10] for j in range(4))
    np.testing.assert_allclose(y, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    # the output at t reads no input after t
    x2 = x.at[:, 5:].set(0.0)
    np.testing.assert_allclose(kda.short_conv(x2, w)[:, :5], y[:, :5])


def test_l2_normalize_gives_unit_rows():
    x = jax.random.normal(jax.random.key(2), (3, 5, 32)) * 7.0
    n = jnp.linalg.norm(kda.l2_normalize(x), axis=-1)
    np.testing.assert_allclose(n, 1.0, rtol=1e-5)
