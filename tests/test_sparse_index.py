"""Learned sparse attention's parts on the CPU (Pallas interpreted):
the flash kernels under a per-query key selection against
``attention_ref`` with the same mask, the index scores and the
indexer's objective against their XLA oracles, and the selection
against ``lax.top_k``'s set.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import sparse_index as si
from apex_tpu.ops.attention import attention_ref, flash_attention

# (batch, q heads, kv heads, sequence, head dim): grouped 8:1 and 2:1,
# one and several blocks, sequences off the block size and off 128
GEOMETRIES = [
    (1, 2, 1, 64, 32), (1, 8, 1, 192, 32), (2, 4, 2, 128, 64),
    (1, 4, 4, 200, 32), (1, 8, 1, 640, 32), (1, 2, 2, 520, 64),
    (2, 8, 1, 384, 16), (1, 4, 1, 1024, 32), (1, 2, 1, 1100, 32),
    (1, 16, 2, 256, 128)]


def _problem(b, h, hk, s, d, seed=0, keep=0.4):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))
    mask = jax.random.bernoulli(ks[3], keep, (b, s, s))
    mask = (mask | jnp.eye(s, dtype=bool)[None]) & jnp.tril(
        jnp.ones((s, s), bool))
    return q, k, v, mask, jax.random.normal(ks[4], (b, h, s, d))


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=str)
def test_masked_flash_forward_and_backward_agree_with_the_oracle(geometry):
    q, k, v, mask, ct = _problem(*geometry)
    add = jnp.where(mask[:, None], 0.0, -1e30)

    def ours(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       key_mask=mask) * ct)

    def oracle(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True, mask=add) * ct)

    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, key_mask=mask),
        attention_ref(q, k, v, causal=True, mask=add), rtol=0, atol=2e-5)
    for got, want in zip(jax.grad(ours, (0, 1, 2))(q, k, v),
                         jax.grad(oracle, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-5)


def test_a_mask_that_selects_every_causal_key_is_causal_attention_bit_for_bit():
    q, k, v, _, _ = _problem(1, 4, 2, 640, 32)
    every = jnp.tril(jnp.ones((1, 640, 640), jnp.int8))
    got, lse = flash_attention(q, k, v, causal=True, key_mask=every,
                               return_lse=True)
    want = flash_attention(q, k, v, causal=True)
    assert bool(jnp.all(got == want))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1),
                        precision="highest") / jnp.sqrt(32.0)
    scores = jnp.where(every[:, None] != 0, scores, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, axis=-1),
                               rtol=0, atol=2e-5)


def test_a_row_that_selects_nothing_gives_zeros_and_no_gradient():
    q, k, v, mask, ct = _problem(1, 2, 1, 192, 32)
    mask = mask.at[:, 7].set(False)
    out = flash_attention(q, k, v, causal=True, key_mask=mask)
    assert float(jnp.max(jnp.abs(out[:, :, 7]))) == 0.0
    dq = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, key_mask=mask) * ct))(q)
    assert float(jnp.max(jnp.abs(dq[:, :, 7]))) == 0.0
    assert bool(jnp.all(jnp.isfinite(dq)))


def test_key_mask_is_not_combined_with_segments_or_dropout():
    q, k, v, mask, _ = _problem(1, 2, 1, 64, 32)
    ids = jnp.ones((1, 64), jnp.int32)
    with pytest.raises(ValueError, match="key_mask"):
        flash_attention(q, k, v, key_mask=mask, segment_ids=(ids, ids))
    with pytest.raises(ValueError, match="key_mask"):
        flash_attention(q, k, v, key_mask=mask, dropout_rate=0.1,
                        dropout_seed=1)
    with pytest.raises(ValueError, match="B, Sq, Sk"):
        flash_attention(q, k, v, key_mask=mask[:, :32])
    with pytest.raises(ValueError, match="return_lse"):
        flash_attention(q, k, v, return_lse=True)


# ---- the indexer -------------------------------------------------------------------

INDEXERS = [(1, 2, 192, 16), (2, 4, 640, 64), (1, 16, 200, 64)]


def _indexer(b, hi, s, di, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, hi, s, di)),
            jax.random.normal(ks[1], (b, s, di)),
            0.1 * jax.random.normal(ks[2], (b, s, hi)),
            jax.random.normal(ks[3], (b, s, s)))


@pytest.mark.parametrize("geometry", INDEXERS, ids=str)
def test_index_scores_and_their_gradients_agree_with_the_oracle(geometry):
    q, k, w, ct = _indexer(*geometry)
    s = geometry[2]
    tri = jnp.tril(jnp.ones((s, s)))
    got, want = si.index_scores(q, k, w), si.index_scores_ref(q, k, w)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert bool(jnp.all(jnp.where(tri == 0, got, -1e30) == -1e30))

    def total(fn):
        return lambda *a: jnp.sum(fn(*a) * ct * tri)
    for g, r in zip(jax.grad(total(si.index_scores), (0, 1, 2))(q, k, w),
                    jax.grad(total(si.index_scores_ref), (0, 1, 2))(q, k, w)):
        np.testing.assert_allclose(g, r, rtol=0,
                                   atol=2e-5 * float(jnp.max(jnp.abs(r))))


@pytest.mark.parametrize("s, topk", [(192, 16), (640, 200), (200, 199)])
def test_the_selection_is_lax_top_ks_set_causal_and_of_the_right_size(s, topk):
    q, k, w, _ = _indexer(2, 2, s, 16, seed=s)
    scores = si.index_scores(q, k, w)
    got = si.select_topk(scores, topk)
    assert got.dtype == jnp.int8
    assert bool(jnp.all(got == si.select_topk_ref(scores, topk)))
    assert bool(jnp.all(jnp.triu(got[0], 1) == 0))             # causal
    np.testing.assert_array_equal(
        jnp.sum(got, axis=-1)[0], np.minimum(topk, np.arange(s) + 1))


def test_equal_scores_at_the_threshold_go_to_the_lower_index():
    q, k, w, _ = _indexer(1, 2, 192, 16)
    tri = jnp.tril(jnp.ones((192, 192), bool))
    coarse = jnp.where(tri, jnp.round(si.index_scores(q, k, w) * 4) / 4,
                       -1e30)
    got = si.select_topk(coarse, 16)
    assert bool(jnp.all(got == si.select_topk_ref(coarse, 16)))
    np.testing.assert_array_equal(
        jnp.sum(got, axis=-1)[0], np.minimum(16, np.arange(192) + 1))
    # all scores equal: the first topk keys
    flat = jnp.where(tri, 0.0, -1e30)[None]
    assert bool(jnp.all(si.select_topk(flat, 16)[0, 100, :16] == 1))
    assert int(jnp.sum(si.select_topk(flat, 16)[0, 100])) == 16


def test_topk_over_the_sequence_selects_every_causal_pair():
    scores = si.index_scores(*_indexer(1, 2, 192, 16)[:3])
    want = jnp.tril(jnp.ones((1, 192, 192), jnp.int8))
    assert bool(jnp.all(si.select_topk(scores, 192) == want))
    assert bool(jnp.all(si.select_topk(scores, 4096) == want))


@pytest.mark.parametrize("h, hk, s, d, topk", [
    (4, 2, 192, 32, 16), (8, 1, 640, 64, 200)])
def test_the_indexers_objective_agrees_with_the_oracle(h, hk, s, d, topk):
    qi, ki, w, _ = _indexer(2, 2, s, 16)
    q, k, v, _, _ = _problem(2, h, hk, s, d, seed=3)
    scores = si.index_scores(qi, ki, w)
    mask = si.select_topk(scores, topk)
    _, lse = flash_attention(q, k, v, causal=True, key_mask=mask,
                             return_lse=True)
    got, grad = jax.value_and_grad(
        lambda x: si.index_loss(x, mask, q, k, lse))(scores)
    want, want_grad = jax.value_and_grad(
        lambda x: si.index_loss_ref(x, mask, q, k))(scores)
    assert float(want) > 0.01
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    tri = jnp.tril(jnp.ones((s, s), bool))
    np.testing.assert_allclose(
        jnp.where(tri, grad, 0), jnp.where(tri, want_grad, 0), rtol=0,
        atol=1e-5 * float(jnp.max(jnp.abs(want_grad))))
    # the main attention is the target, not a participant
    dq = jax.grad(lambda q: si.index_loss(scores, mask, q, k, lse))(q)
    assert float(jnp.max(jnp.abs(dq))) == 0.0


def test_loss_and_grad_are_the_objectives_one_pass_and_send_nothing_back():
    """``index_loss_and_grad``: the value is ``index_loss``'s, ``g`` its
    gradient in the scores times the number of rows, and neither is
    differentiable in anything."""
    b, s = 2, 192
    qi, ki, w, _ = _indexer(b, 2, s, 16)
    q, k, v, _, _ = _problem(b, 4, 2, s, 32, seed=3)
    scores = si.index_scores(qi, ki, w)
    mask = si.select_topk(scores, 16)
    _, lse = flash_attention(q, k, v, causal=True, key_mask=mask,
                             return_lse=True)
    want, want_grad = jax.value_and_grad(
        lambda x: si.index_loss(x, mask, q, k, lse))(scores)
    got, g = si.index_loss_and_grad(scores, mask, q, k, lse)
    assert float(got) == float(want)
    np.testing.assert_allclose(g / (b * s), want_grad, rtol=1e-6, atol=0)
    for i in range(2):
        d = jax.grad(lambda x: jnp.sum(si.index_loss_and_grad(
            x, mask, q, k, lse)[i]))(scores)
        assert float(jnp.max(jnp.abs(d))) == 0.0
