"""Fused/flash attention + ring attention vs the XLA oracle (reference
models: apex/contrib/test/multihead_attn + fmha suites)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import comm
from apex_tpu.ops import attention as attn


def qkv(key, b=2, h=2, s=64, d=128, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, h, s, d), jnp.float32
                                     ).astype(dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(causal, dtype):
    q, k, v = qkv(jax.random.key(0), dtype=dtype)
    o = attn.flash_attention(q, k, v, causal)
    want = attn.attention_ref(q, k, v, causal=causal)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_match_ref(causal):
    q, k, v = qkv(jax.random.key(1), s=32)

    def f(q, k, v):
        return jnp.sum(attn.flash_attention(q, k, v, causal) ** 2)

    def fr(q, k, v):
        return jnp.sum(attn.attention_ref(q, k, v, causal=causal) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("d", [64, 80])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_unaligned_head_dim(causal, d):
    """Real head dims (64, 80) take the lane-padded kernel path; values
    and grads must still match the oracle."""
    q, k, v = qkv(jax.random.key(7), s=32, d=d)
    o = attn.flash_attention(q, k, v, causal)
    want = attn.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda *a: jnp.sum(
        attn.flash_attention(*a, causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        attn.attention_ref(*a, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-5)


def test_flash_attention_cross_lengths():
    """Encoder-decoder shape: Sq != Sk."""
    kq, kk = jax.random.split(jax.random.key(2))
    q = jax.random.normal(kq, (2, 2, 24, 128))
    k = jax.random.normal(kk, (2, 2, 56, 128))
    v = jax.random.normal(jax.random.key(3), (2, 2, 56, 128))
    o = attn.flash_attention(q, k, v, False)
    want = attn.attention_ref(q, k, v)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    """Sequence sharded over the ctx axis == unsharded attention."""
    mesh = comm.initialize(data=2, ctx=4)
    b, h, s, d = 2, 2, 32, 16   # s sharded 4-way
    q = jax.random.normal(jax.random.key(4), (b, h, s, d))
    k = jax.random.normal(jax.random.key(5), (b, h, s, d))
    v = jax.random.normal(jax.random.key(6), (b, h, s, d))

    def f(q, k, v):
        return attn.ring_attention(q, k, v, causal=causal)

    o = jax.jit(comm.shard_map(
        f, mesh,
        in_specs=(P(None, None, comm.AXIS_CTX, None),) * 3,
        out_specs=P(None, None, comm.AXIS_CTX, None)))(q, k, v)
    want = attn.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_grads_match_full():
    mesh = comm.initialize(data=2, ctx=4)
    b, h, s, d = 1, 2, 16, 8
    q = jax.random.normal(jax.random.key(7), (b, h, s, d))
    k = jax.random.normal(jax.random.key(8), (b, h, s, d))
    v = jax.random.normal(jax.random.key(9), (b, h, s, d))

    def f(q, k, v):
        return jnp.sum(attn.ring_attention(q, k, v, causal=True) ** 2)

    g = jax.jit(comm.shard_map(
        jax.grad(f, argnums=(0, 1, 2)), mesh,
        in_specs=(P(None, None, comm.AXIS_CTX, None),) * 3,
        out_specs=(P(None, None, comm.AXIS_CTX, None),) * 3))(q, k, v)

    def fr(q, k, v):
        return jnp.sum(attn.attention_ref(q, k, v, causal=True) ** 2)

    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_ids(causal):
    """Segment masking (the fmha contract): cross-segment pairs masked,
    tokens with unmatched ids produce zero rows."""
    b, h, s, d = 2, 2, 64, 128
    q, k, v = qkv(jax.random.key(3), b=b, h=h, s=s, d=d)
    seg = jnp.concatenate([jnp.zeros((b, 24), jnp.int32),
                           jnp.ones((b, 24), jnp.int32),
                           jnp.full((b, 16), 2, jnp.int32)], axis=1)
    q_ids = jnp.where(jnp.arange(s)[None] < 56, seg, -1)
    kv_ids = jnp.where(jnp.arange(s)[None] < 56, seg, -2)
    o = attn.flash_attention(q, k, v, causal,
                             segment_ids=(q_ids, kv_ids))
    same = q_ids[:, None, :, None] == kv_ids[:, None, None, :]
    mask = jnp.where(same, 0.0, -1e30)
    want = attn.attention_ref(q, k, v, causal=causal, mask=mask)
    # fully-masked q rows: kernel gives exact zeros
    want = jnp.where((jnp.arange(s) < 56)[None, None, :, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_segment_ids_grads():
    b, h, s, d = 1, 2, 64, 64
    q, k, v = qkv(jax.random.key(4), b=b, h=h, s=s, d=d)
    seg = (jnp.arange(s)[None] >= 32).astype(jnp.int32)
    ids = (seg, seg)
    same = seg[:, None, :, None] == seg[:, None, None, :]
    mask = jnp.where(same, 0.0, -1e30)

    g = jax.grad(lambda *a: jnp.sum(
        attn.flash_attention(*a, segment_ids=ids) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        attn.attention_ref(*a, mask=mask) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_multiblock_tiling(causal, monkeypatch):
    """Sequences spanning multiple 128-blocks and a non-divisible
    length (footprint of the K-tiled online-softmax rework).  The cap
    is what makes it multi-block: at the default one, s=320 would run
    as ONE 384-row block."""
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "128")
    q, k, v = qkv(jax.random.key(5), b=1, h=1, s=320, d=64)
    assert attn._geom(q, k)[6:10] == (128, 128, 384, 384)   # 3 x 3
    o = attn.flash_attention(q, k, v, causal)
    want = attn.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda *a: jnp.sum(
        attn.flash_attention(*a, causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        attn.attention_ref(*a, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=5e-5)


# causal geometries that between them hold every block class (interior,
# diagonal, not visited; a padded last block on either side; a kv block
# no q row reaches): id -> (cap or None for the default 512, b, h, hk,
# sq, sk, d, dtype, segments, dropout rate)
_CAUSAL_GEOMETRIES = {
    "3x3": ("128", 1, 2, 2, 384, 384, 64, jnp.float32, False, 0.0),
    "4x4_bf16_d128": ("128", 1, 2, 2, 512, 512, 128, jnp.bfloat16,
                      False, 0.0),
    # bq 384 / bk 512: 1 x 2 blocks, the second kv block above every row
    "bq_ne_bk": ("512", 1, 2, 2, 384, 1024, 64, jnp.float32, False, 0.0),
    # bq 512 / bk 128 (1152 has no 512-divisor): 3 x 9 blocks
    "bq512_bk128": (None, 1, 1, 1, 1536, 1152, 64, jnp.float32, False,
                    0.0),
    # bq 512 / bk 384: 2 x 1 blocks, the forward's one-kv-block body
    "sq_gt_sk": ("512", 1, 2, 2, 1024, 384, 64, jnp.float32, False, 0.0),
    "ragged": ("128", 2, 2, 2, 320, 320, 64, jnp.float32, False, 0.0),
    "ragged_sq_ne_sk_bf16": ("128", 1, 2, 2, 300, 450, 64, jnp.bfloat16,
                             False, 0.0),
    "segments": ("128", 2, 2, 2, 384, 384, 64, jnp.float32, True, 0.0),
    "gqa": ("128", 1, 4, 2, 384, 384, 64, jnp.float32, False, 0.0),
    "mqa_ragged": ("128", 1, 4, 1, 320, 320, 64, jnp.float32, False, 0.0),
    "dropout": ("128", 1, 2, 2, 384, 384, 64, jnp.float32, False, 0.2),
    "gqa_dropout_segments_bf16": ("128", 2, 4, 2, 320, 320, 64,
                                  jnp.bfloat16, True, 0.1),
    # larger blocks: 256 (3 x 3; 2 x 4; ragged) and the default 512
    "3x3_at_256": ("256", 1, 2, 2, 768, 768, 64, jnp.float32, False, 0.0),
    "2x2_at_512_bf16_d128": ("512", 1, 1, 1, 1024, 1024, 128,
                             jnp.bfloat16, False, 0.0),
    "sq_lt_sk_at_256": ("256", 1, 2, 2, 512, 1024, 64, jnp.float32,
                        False, 0.0),
    "ragged_gqa_dropout_segments_at_256": ("256", 2, 4, 2, 700, 700, 64,
                                           jnp.float32, True, 0.1),
    # 1024 tiles with sq != sk: 2 x 1 (the forward's one-kv-block body;
    # dq and dkv on the flattened grid) and 1 x 2 (the second kv block
    # above every row: one masked visit in dkv)
    "sq_gt_sk_at_1024": ("1024", 1, 2, 1, 2048, 1024, 64, jnp.bfloat16,
                         False, 0.0),
    "sq_lt_sk_at_1024": ("1024", 1, 1, 1, 1024, 2048, 64, jnp.bfloat16,
                         False, 0.0),
}


@pytest.mark.parametrize("geometry", sorted(_CAUSAL_GEOMETRIES))
def test_causal_block_classes_match_ref(geometry, monkeypatch):
    """Causal forward and all three gradients against the oracle where
    the grid holds every class of block — interior, diagonal, never
    visited — with padding, segments, grouped heads and dropout (whose
    mask hashes the TRUE block position, not the flattened grid's
    step)."""
    from apex_tpu.ops import _dispatch

    cap, b, h, hk, sq, sk, d, dtype, seg, rate = \
        _CAUSAL_GEOMETRIES[geometry]
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {})
    if cap is None:
        monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    else:
        monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", cap)
    ks = jax.random.split(jax.random.key(30), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hk, sk, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hk, sk, d)).astype(dtype)
    bq, bk = attn._geom(q, k)[6:8]
    plan = attn.causal_block_plan(sq, sk, bq, bk)
    assert plan.nq * plan.nk > 1 and plan.diagonal > 0
    if geometry not in ("bq_ne_bk", "sq_gt_sk", "sq_gt_sk_at_1024",
                        "sq_lt_sk_at_1024"):
        assert plan.interior > 0 and plan.not_visited > 0

    kw, ref_kw = dict(causal=True), dict(causal=True)
    if rate:
        kw.update(dropout_rate=rate, dropout_seed=jnp.int32(77))
        ref_kw.update(kw)
    if seg:     # three uneven segments, boundaries inside blocks
        ids = jnp.asarray(np.repeat(
            [1, 2, 3], [sq // 4, sq // 2, sq - sq // 4 - sq // 2])[None],
            jnp.int32) * jnp.ones((b, 1), jnp.int32)
        same = ids[:, None, :, None] == ids[:, None, None, :]
        kw.update(segment_ids=(ids, ids))
        ref_kw.update(mask=jnp.where(same, 0.0, attn._NEG))

    def loss(f, kwargs):
        return lambda *a: jnp.sum(
            f(*a, **kwargs).astype(jnp.float32) ** 2)

    got = (attn.flash_attention(q, k, v, **kw),) + jax.grad(
        loss(attn.flash_attention, kw), argnums=(0, 1, 2))(q, k, v)
    want = (attn.attention_ref(q, k, v, **ref_kw),) + jax.grad(
        loss(attn.attention_ref, ref_kw), argnums=(0, 1, 2))(q, k, v)
    assert got[2].shape == (b, hk, sk, d) == got[3].shape
    for n, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == jnp.bfloat16:
            # the file's bf16 tolerance, in units of the oracle's range
            unit = max(float(np.abs(w).max()), 1.0)
            tol = dict(rtol=2e-2, atol=2e-2 * unit)
        else:
            tol = dict(rtol=2e-5, atol=2e-5) if n == 0 else \
                dict(rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(g, w, **tol)


def test_causal_block_plan_hand_worked():
    """The plan is the one place the causal grids, kernel_bench and the
    tests read: hand-worked counts, both orders, padding, coverage."""
    plan = attn.causal_block_plan(4096, 4096, 512, 512)
    assert (plan.nq, plan.nk) == (8, 8)
    assert (plan.interior, plan.diagonal, plan.not_visited) == (28, 8, 28)
    assert plan.q_major[:4] == ((0, 0, False), (1, 0, True),
                                (1, 1, False), (2, 0, True))
    assert plan.q_major[-1] == (7, 7, False) and len(plan.q_major) == 36
    assert plan.kv_major[:9] == tuple(
        (j, 0, j > 0) for j in range(8)) + ((1, 1, False),)
    assert plan.kv_major[-1] == (7, 7, False)
    assert sorted(plan.kv_major) == sorted(plan.q_major)

    # bq 384 / bk 512 over sq 384 / sk 1024: one q block; kv block 1
    # lies above every row: never visited forward, one masked visit in
    # the kv-major order (its dk/dv must be written)
    plan = attn.causal_block_plan(384, 1024, 384, 512)
    assert (plan.nq, plan.nk) == (1, 2)
    assert plan.q_major == ((0, 0, False),)
    assert plan.kv_major == ((0, 0, False), (0, 1, False))
    assert (plan.interior, plan.diagonal, plan.not_visited) == (0, 1, 1)

    # bq 256 / bk 128 over 512 x 512: the diagonal crosses two kv
    # blocks of every q block
    plan = attn.causal_block_plan(512, 512, 256, 128)
    assert plan.q_major == ((0, 0, False), (0, 1, False),
                            (1, 0, True), (1, 1, True),
                            (1, 2, False), (1, 3, False))
    assert (plan.interior, plan.diagonal, plan.not_visited) == (2, 4, 2)

    # s 320 in 128-blocks: the last q block holds padded rows (and the
    # last kv block padded columns), so none of its blocks is interior
    plan = attn.causal_block_plan(320, 320, 128, 128)
    assert plan.q_major == ((0, 0, False), (1, 0, True), (1, 1, False),
                            (2, 0, False), (2, 1, False), (2, 2, False))
    # sk 200 in 128-blocks under sq 384: kv block 1 is below the
    # diagonal for q block 2 and still not interior (56 padded columns)
    assert attn.causal_block_plan(384, 200, 128, 128).q_major[-1] == \
        (2, 1, False)

    # every (row, column) with column <= row lies in exactly one visited
    # block; an interior block holds no other kind of position
    for sq, sk, bq, bk in [(4096, 4096, 512, 512), (384, 1024, 384, 512),
                           (512, 512, 256, 128), (300, 450, 128, 128),
                           (1536, 1152, 512, 128), (1024, 384, 512, 384)]:
        plan = attn.causal_block_plan(sq, sk, bq, bk)
        seen = np.zeros((sq, sk), np.int32)
        for j, kk, interior in plan.q_major:
            r = slice(j * bq, min((j + 1) * bq, sq))
            c = slice(kk * bk, min((kk + 1) * bk, sk))
            seen[r, c] += 1
            if interior:
                assert (kk + 1) * bk <= sk and (j + 1) * bq <= sq
                assert np.tril(np.ones((sq, sk), bool))[r, c].all()
        counts = np.tril(seen)
        assert (counts[np.tril(np.ones((sq, sk), bool))] == 1).all()
        assert plan.interior + plan.diagonal + plan.not_visited == \
            plan.nq * plan.nk
        # the kv-major order visits the same blocks, plus one masked
        # visit for a kv block no row reaches
        extra = set(plan.kv_major) - set(plan.q_major)
        assert set(plan.q_major) <= set(plan.kv_major)
        assert all(j == plan.nq - 1 and not interior and kk * bk >= sq
                   for j, kk, interior in extra)
        assert sorted({p[1] for p in plan.kv_major}) == list(range(plan.nk))


# the geometry rule (PR 32), one case per clause: id -> (sq, sk, d,
# dtype, APEX_TPU_ATTN_BLOCK_CAP or None, prefs table, (bq, bk) wanted)
_BF16, _F32 = jnp.bfloat16, jnp.float32
_GEOMETRY_RULE = {
    # BERT's cell: one block whatever the cap
    "bert_s512_d64": (512, 512, 64, _BF16, None, {}, (512, 512)),
    # the looped and the sparse-attention cells: 1024 tiles
    "looped_s4096_d128": (4096, 4096, 128, _BF16, None, {}, (1024, 1024)),
    "expert_s8192_d128": (8192, 8192, 128, _BF16, None, {}, (1024, 1024)),
    "s2048_d64_two_tiles": (2048, 2048, 64, _BF16, None, {}, (1024, 1024)),
    # not longer than one 1024 tile: what it had
    "s1024_keeps_512": (1024, 1024, 128, _BF16, None, {}, (512, 512)),
    # each side by its own length
    "sq512_sk4096": (512, 4096, 128, _BF16, None, {}, (512, 1024)),
    # 1024 does not divide: 512, never 128
    "s1536": (1536, 1536, 128, _BF16, None, {}, (512, 512)),
    "s2560": (2560, 2560, 128, _BF16, None, {}, (512, 512)),
    "s3584": (3584, 3584, 64, _BF16, None, {}, (512, 512)),
    "s1500_pads_to_1536": (1500, 1500, 128, _BF16, None, {}, (512, 512)),
    # the largest block that divides, where 512 does not either
    "s1280_at_256": (1280, 1280, 128, _BF16, None, {}, (256, 256)),
    "s1152_at_128": (1152, 1152, 128, _BF16, None, {}, (128, 128)),
    # short and ragged: one block of the padded length
    "s320_one_block": (320, 320, 64, _BF16, None, {}, (384, 384)),
    # float32 operands dot at HIGHEST: unmeasured at 1024, keep 512
    "s4096_f32": (4096, 4096, 128, _F32, None, {}, (512, 512)),
    "s1536_f32": (1536, 1536, 64, _F32, None, {}, (512, 512)),
    # wider heads as before
    "dp256": (4096, 4096, 256, _BF16, None, {}, (256, 256)),
    "dp256_from_d160": (4096, 4096, 160, _BF16, None, {}, (256, 256)),
    "dp384": (4096, 4096, 384, _BF16, None, {}, (128, 128)),
    # the variable, then the prefs table, then the default
    "env_512_wins": (4096, 4096, 128, _BF16, "512", {"128": 256},
                     (512, 512)),
    "env_128_wins_f32": (4096, 4096, 128, _F32, "128", {}, (128, 128)),
    "env_1024_over_s1536": (1536, 1536, 128, _BF16, "1024", {},
                            (512, 512)),
    "table_256_wins": (4096, 4096, 128, _BF16, None, {"128": 256},
                       (256, 256)),
    "table_512_over_default_1024": (8192, 8192, 128, _BF16, None,
                                    {"128": 512}, (512, 512)),
    "table_other_dp": (4096, 4096, 128, _BF16, None, {"256": 128},
                       (1024, 1024)),
    "table_clamped_dp256": (4096, 4096, 256, _BF16, None, {"256": 1024},
                            (512, 512)),
}


@pytest.mark.parametrize("case", sorted(_GEOMETRY_RULE))
def test_block_geometry_rule(case, monkeypatch):
    """``_geom``'s tile from head dim, operand width and sequence
    length; the variable and the prefs table still win, in that order.
    Pure: no kernel runs."""
    import warnings

    from apex_tpu.ops import _dispatch

    sq, sk, d, dtype, env, table, want = _GEOMETRY_RULE[case]
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", table)
    if env is None:
        monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    else:
        monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", env)
    q = jax.ShapeDtypeStruct((1, 2, sq, d), dtype)
    k = jax.ShapeDtypeStruct((1, 2, sk, d), dtype)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        b, h, sq_, sk_, d_, dp, bq, bk, sqp, skp = attn._geom(q, k)
    assert (bq, bk) == want
    assert (sqp, skp) == (-(-sq // 128) * 128, -(-sk // 128) * 128)
    assert sqp % bq == 0 and skp % bk == 0
    # what the rule before PR 32 tiled at 512 is never tiled smaller
    if env is None and not table and dp == 128:
        for s_, blk in ((sqp, bq), (skp, bk)):
            if s_ % 512 == 0:
                assert blk >= 512
    # a cap the operator asked for and did not get is said aloud
    assert bool(caught) == (case == "env_1024_over_s1536")


@pytest.mark.parametrize("s,visited,interior,diagonal,not_visited",
                         [(2048, 3, 1, 2, 1), (4096, 10, 6, 4, 6),
                          (8192, 36, 28, 8, 28)])
def test_causal_block_plan_at_1024(s, visited, interior, diagonal,
                                   not_visited):
    """The cells' grids at the new tile: looped s4096 visits 10 of 16
    tiles, 4 of them diagonal; the expert cell's s8192 36 of 64, 8."""
    plan = attn.causal_block_plan(s, s, 1024, 1024)
    assert len(plan.q_major) == visited == len(plan.kv_major)
    assert (plan.interior, plan.diagonal, plan.not_visited) == (
        interior, diagonal, not_visited)
    assert all((j == kk) != inner for j, kk, inner in plan.q_major)


# numerical cases at the default tile of long 16-bit sequences: id ->
# (h, hk, s, want block, key selection, segments, dropout rate)
_TILE_1024_CASES = {
    "causal_s2048": (2, 2, 2048, 1024, False, False, 0.0),
    "key_mask_gqa_s2048": (4, 1, 2048, 1024, True, False, 0.0),
    "segments_s2048": (1, 1, 2048, 1024, False, True, 0.0),
    "ragged_gqa_dropout_segments_s2000": (2, 1, 2000, 1024, False, True,
                                          0.1),
    "s1536_steps_down_to_512": (1, 1, 1536, 512, False, False, 0.0),
}


@pytest.mark.parametrize("case", sorted(_TILE_1024_CASES))
def test_default_tile_matches_ref(case, monkeypatch):
    """bf16 d128 at the DEFAULT geometry (no cap forced): two 1024
    tiles a side — one interior, two diagonal — forward and all three
    gradients against the oracle; under a key selection with grouped
    heads, with segments (a 1024 tile tiles the 128-lane segment row 8
    times), with padding, grouped heads and dropout together, and at a
    length 1024 does not divide."""
    from apex_tpu.ops import _dispatch

    h, hk, s, blk, sel, seg, rate = _TILE_1024_CASES[case]
    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {})
    ks = jax.random.split(jax.random.key(32), 5)
    q = jax.random.normal(ks[0], (1, h, s, 128)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, hk, s, 128)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, hk, s, 128)).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], (1, h, s, 128))
    assert attn._geom(q, k)[6:8] == (blk, blk)

    kw, ref_kw = dict(causal=True), dict(causal=True)
    if rate:
        kw.update(dropout_rate=rate, dropout_seed=jnp.int32(77))
        ref_kw.update(kw)
    if sel:     # a selection that keeps the diagonal, so no row is empty
        mask = (jax.random.bernoulli(ks[4], 0.3, (1, s, s))
                | jnp.eye(s, dtype=bool)[None])
        kw.update(key_mask=mask)
        ref_kw.update(mask=jnp.where(mask[:, None], 0.0, attn._NEG))
    if seg:     # three uneven segments, boundaries inside the tiles
        ids = jnp.asarray(np.repeat([1, 2, 3], [700, 900, s - 1600])[None],
                          jnp.int32)
        same = ids[:, None, :, None] == ids[:, None, None, :]
        kw.update(segment_ids=(ids, ids))
        ref_kw.update(mask=jnp.where(same, 0.0, attn._NEG))

    def loss(f, kwargs):
        return lambda *a: jnp.sum(f(*a, **kwargs).astype(jnp.float32) * ct)

    got = (attn.flash_attention(q, k, v, **kw),) + jax.grad(
        loss(attn.flash_attention, kw), argnums=(0, 1, 2))(q, k, v)
    want = (attn.attention_ref(q, k, v, **ref_kw),) + jax.grad(
        loss(attn.attention_ref, ref_kw), argnums=(0, 1, 2))(q, k, v)
    assert got[2].shape == (1, hk, s, 128) == got[3].shape
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        # the file's bf16 tolerance, in units of the oracle's range
        unit = max(float(np.abs(w).max()), 1.0)
        np.testing.assert_allclose(g, w, rtol=2e-2, atol=2e-2 * unit)


@pytest.mark.parametrize("causal", [False, True])
def test_single_kv_fast_path_matches_generic_kernel(causal,
                                                    monkeypatch):
    """The nk==1 scratch-free forward (round 5) vs the generic online
    kernel on the SAME inputs — kernel-to-kernel, tighter than the
    oracle-tolerance grids: forcing a 128 cap makes the same s=256
    shape tile as two KV blocks through the generic body."""
    from apex_tpu.ops import _dispatch

    # pin the geometry sources: a dev-shell cap export or a measured
    # table entry would silently tile BOTH legs multi-block and the
    # comparison would cover nothing
    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {})
    q, k, v = qkv(jax.random.key(9), b=1, h=2, s=256, d=64)

    def fwd_and_grads():
        o = attn.flash_attention(q, k, v, causal)
        g = jax.grad(lambda *a: jnp.sum(
            attn.flash_attention(*a, causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        return (o,) + g

    assert attn._geom(q, k)[7] == 256       # bk covers skp: nk == 1
    fast = fwd_and_grads()          # default cap 512 -> nk == 1
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "128")
    assert attn._geom(q, k)[7] == 128       # forced: nk == 2
    generic = fwd_and_grads()
    for a, b_ in zip(fast, generic):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_kernel_matches_ring_ref(causal):
    """The flash-kernel ring == the jnp blockwise ring (fwd + grads),
    on multi-128-block per-shard lengths."""
    b, h, s, d = 1, 2, 8 * 256, 64     # 256 tokens per ctx shard
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    mesh = comm.initialize(data=1, ctx=8, model=1)
    spec = P(None, None, "ctx")

    def mk(f):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v, causal=causal)
                           .astype(jnp.float32) ** 2) / s
        return jax.jit(comm.shard_map(
            lambda q, k, v: (loss(q, k, v),
                             jax.grad(loss, argnums=(0, 1, 2))(q, k, v)),
            mesh, in_specs=(spec,) * 3, out_specs=(P(), (spec,) * 3)))

    l1, g1 = mk(attn.ring_attention)(q, k, v)
    l2, g2 = mk(attn.ring_attention_ref)(q, k, v)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    """all_to_all sequence parallelism == unsharded attention."""
    mesh = comm.initialize(data=2, ctx=4)
    b, h, s, d = 2, 4, 32, 16   # h and s both divisible by ctx=4
    q = jax.random.normal(jax.random.key(20), (b, h, s, d))
    k = jax.random.normal(jax.random.key(21), (b, h, s, d))
    v = jax.random.normal(jax.random.key(22), (b, h, s, d))

    def f(q, k, v):
        return attn.ulysses_attention(q, k, v, causal=causal)

    o = jax.jit(comm.shard_map(
        f, mesh,
        in_specs=(P(None, None, comm.AXIS_CTX, None),) * 3,
        out_specs=P(None, None, comm.AXIS_CTX, None)))(q, k, v)
    want = attn.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ulysses_attention_grads_match_full():
    mesh = comm.initialize(data=2, ctx=4)
    b, h, s, d = 1, 4, 16, 8
    q = jax.random.normal(jax.random.key(23), (b, h, s, d))
    k = jax.random.normal(jax.random.key(24), (b, h, s, d))
    v = jax.random.normal(jax.random.key(25), (b, h, s, d))

    def f(q, k, v):
        # per-shard local loss: the shard losses sum to the global one,
        # so the transposed all_to_alls accumulate exactly the full
        # gradient (same pattern as the ring-attention grads test)
        return jnp.sum(attn.ulysses_attention(q, k, v, causal=True) ** 2)

    g = jax.jit(comm.shard_map(
        jax.grad(f, argnums=(0, 1, 2)), mesh,
        in_specs=(P(None, None, comm.AXIS_CTX, None),) * 3,
        out_specs=(P(None, None, comm.AXIS_CTX, None),) * 3))(q, k, v)

    def fr(q, k, v):
        return jnp.sum(attn.attention_ref(q, k, v, causal=True) ** 2)

    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_attention_rejects_indivisible_heads():
    mesh = comm.initialize(ctx=4)
    q = jax.random.normal(jax.random.key(26), (1, 3, 16, 8))  # h=3

    def f(q):
        return attn.ulysses_attention(q, q, q)

    with pytest.raises(ValueError, match="divisible"):
        jax.jit(comm.shard_map(
            f, mesh, in_specs=(P(None, None, comm.AXIS_CTX, None),),
            out_specs=P(None, None, comm.AXIS_CTX, None)))(q)


def test_attn_block_cap_env_knob(monkeypatch):
    """APEX_TPU_ATTN_BLOCK_CAP (swept by kernel_bench --sweep-attn on
    hardware) overrides the default geometry; bad values fail loudly;
    the kernel stays correct at a non-default cap."""
    from apex_tpu.ops import attention as A

    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    q = jnp.zeros((1, 1, 512, 64), jnp.float32)
    k = jnp.zeros((1, 1, 512, 64), jnp.float32)
    assert A._geom(q, k)[6] == 512            # default cap at dp=128
    # a cap above the padded length clamps to one block, not 128
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "1024")
    assert A._geom(q, k)[6] == 512
    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP")
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "256")
    assert A._geom(q, k)[6] == 256
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "100")
    with pytest.raises(ValueError, match="multiple of 128"):
        A._geom(q, k)
    # correctness at a GENUINELY overridden geometry: s=512 with
    # cap=128 tiles 4x4 blocks where the default cap (512) would run a
    # single block — a silently ignored env var would not change tiling
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "128")
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 512, 64)) for kk in ks)
    assert A._geom(q, k)[6] == 128            # bq actually overridden
    got = A.flash_attention(q, k, v, causal=True)
    want = A.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hk,causal", [(2, False), (2, True),
                                       (1, True), (4, False)])
def test_gqa_flash_matches_repeated_kv_oracle(hk, causal):
    """Grouped-query / multi-query attention (beyond-reference): the
    kernel reads the small K/V directly (no repeat materialization);
    output and all grads must match the repeat-kv oracle, with dk/dv
    summed over each kv head's q group."""
    from apex_tpu.ops import attention as A

    b, h, s, d = 2, 4, 256, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))

    got = A.flash_attention(q, k, v, causal=causal)
    want = A.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    gq, gk, gv = jax.grad(loss(A.flash_attention),
                          argnums=(0, 1, 2))(q, k, v)
    oq, ok, ov = jax.grad(loss(A.attention_ref),
                          argnums=(0, 1, 2))(q, k, v)
    assert gk.shape == (b, hk, s, d) and gv.shape == (b, hk, s, d)
    for g, o in ((gq, oq), (gk, ok), (gv, ov)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


def test_gqa_dropout_segments_compose():
    """The three attention extensions TOGETHER — grouped-query heads,
    fused probability dropout, and packed-segment masking — against
    the oracle (which repeats kv, applies the same hash mask, and
    masks cross-segment): fwd and all grads.  Pairwise combinations
    have their own tests; this pins the triple."""
    from apex_tpu.ops import attention as A

    b, h, hk, s, d = 1, 4, 2, 128, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))
    ids = jnp.asarray(np.repeat([1, 2], [60, 68])[None, :], jnp.int32)
    seed = jnp.int32(77)
    kw = dict(causal=True, dropout_rate=0.25, dropout_seed=seed)

    def ref(q, k, v):
        same = ids[:, None, :, None] == ids[:, None, None, :]
        return A.attention_ref(q, k, v,
                               mask=jnp.where(same, 0.0, A._NEG), **kw)

    got = A.flash_attention(q, k, v, segment_ids=(ids, ids), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    gs = jax.grad(lambda q, k, v: jnp.sum(A.flash_attention(
        q, k, v, segment_ids=(ids, ids), **kw
    ).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
    os_ = jax.grad(lambda q, k, v: jnp.sum(
        ref(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert gs[1].shape == (b, hk, s, d)
    for g, o in zip(gs, os_):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


def test_gqa_with_segment_ids_and_padding():
    """GQA composes with packed-batch masking and non-128-multiple
    sequence lengths (padded geometry)."""
    from apex_tpu.ops import attention as A

    b, h, hk, s, d = 1, 4, 2, 200, 64
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))
    ids = jnp.asarray(
        np.repeat([0, 1, 2], [80, 70, 50])[None, :], jnp.int32)

    got = A.flash_attention(q, k, v, segment_ids=(ids, ids))
    same = ids[:, None, :, None] == ids[:, None, None, :]
    want = A.attention_ref(q, k, v, mask=jnp.where(same, 0.0, A._NEG))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # grads: the dkv seg BlockSpecs batch-index by KV-head grid rows
    # (i // hk, not i // h) — only wrong when hk < h AND segments are
    # set, so pin exactly that combination
    gq, gk, gv = jax.grad(
        lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, segment_ids=(ids, ids)).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    oq, ok, ov = jax.grad(
        lambda q, k, v: jnp.sum(A.attention_ref(
            q, k, v, mask=jnp.where(same, 0.0, A._NEG)
        ).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert gk.shape == (b, hk, s, d)
    for g, o in ((gq, oq), (gk, ok), (gv, ov)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


def test_gqa_rejects_indivisible_heads():
    from apex_tpu.ops import attention as A

    q = jnp.zeros((1, 4, 128, 64))
    kv = jnp.zeros((1, 3, 128, 64))
    with pytest.raises(ValueError, match="multiple of kv heads"):
        A.flash_attention(q, kv, kv)
    # the ring's blockwise math is head-aligned with q: GQA shapes must
    # refuse loudly up front, not break in backward
    kv2 = jnp.zeros((1, 2, 128, 64))
    with pytest.raises(ValueError, match="equal q/kv head counts"):
        A.ring_attention(q, kv2, kv2)


@pytest.mark.parametrize("causal,hk", [(False, 4), (True, 4), (True, 2)])
def test_fused_dropout_matches_oracle(causal, hk):
    """Fused hash-mask dropout: the kernel and the jnp oracle share
    _keep_mask, so outputs and ALL grads must agree elementwise (the
    backward kernels reconstruct the identical mask from coordinates;
    GQA composes — the dkv kernel re-derives the flat q row)."""
    from apex_tpu.ops import attention as A

    b, h, s, d = 2, 4, 256, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hk, s, d))
    v = jax.random.normal(ks[2], (b, hk, s, d))
    seed = jnp.int32(77)

    kw = dict(causal=causal, dropout_rate=0.25, dropout_seed=seed)
    got = A.flash_attention(q, k, v, **kw)
    want = A.attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss(f):
        return lambda *a: jnp.sum(f(*a, **kw).astype(jnp.float32) ** 2)

    g = jax.grad(loss(A.flash_attention), argnums=(0, 1, 2))(q, k, v)
    o = jax.grad(loss(A.attention_ref), argnums=(0, 1, 2))(q, k, v)
    assert g[1].shape == (b, hk, s, d)
    for a_, b_ in zip(g, o):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_fused_dropout_mask_properties():
    """Keep rate ~= 1-rate; same seed -> identical mask; different
    seed -> different mask; rate 0 -> identity with no seed needed."""
    from apex_tpu.ops import attention as A

    keep = A.dropout_keep_ref(jnp.int32(5), 2, 4, 128, 128, 0.3)
    frac = float(jnp.mean(keep.astype(jnp.float32)))
    assert abs(frac - 0.7) < 0.01, frac
    keep2 = A.dropout_keep_ref(jnp.int32(5), 2, 4, 128, 128, 0.3)
    assert bool(jnp.all(keep == keep2))
    keep3 = A.dropout_keep_ref(jnp.int32(6), 2, 4, 128, 128, 0.3)
    assert float(jnp.mean((keep != keep3).astype(jnp.float32))) > 0.1

    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 64)) for kk in ks)
    o0 = A.flash_attention(q, k, v, dropout_rate=0.0)
    o_plain = A.flash_attention(q, k, v)
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o_plain))

    with pytest.raises(ValueError, match="requires dropout_seed"):
        A.flash_attention(q, k, v, dropout_rate=0.1)
    with pytest.raises(ValueError, match="must be in"):
        A.flash_attention(q, k, v, dropout_rate=1.0,
                          dropout_seed=jnp.int32(0))


def test_fused_dropout_with_segment_ids():
    """Dropout composes with packed-batch masking: cross-segment pairs
    stay zero regardless of the dropout mask."""
    from apex_tpu.ops import attention as A

    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.key(13), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    ids = (jnp.arange(s)[None] // 64).astype(jnp.int32)
    seed = jnp.int32(3)

    got = A.flash_attention(q, k, v, segment_ids=(ids, ids),
                            dropout_rate=0.2, dropout_seed=seed)
    same = ids[:, None, :, None] == ids[:, None, None, :]
    want = A.attention_ref(q, k, v, mask=jnp.where(same, 0.0, A._NEG),
                           dropout_rate=0.2, dropout_seed=seed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_dropout_dispatch_stable(monkeypatch):
    """The escape-hatch XLA path drops the SAME elements as the kernel
    (both hash the same coordinates), so flipping the dispatch gate
    never changes training behavior."""
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops import attention as A

    ks = jax.random.split(jax.random.key(17), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 64)) for kk in ks)
    kw = dict(causal=True, dropout_rate=0.3,
              dropout_seed=jnp.int32(123))
    o_kernel = A.flash_attention(q, k, v, **kw)
    monkeypatch.setattr(_dispatch, "_PREFS",
                        {"attention_f32": False, "attention": False})
    o_xla = A.flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_xla),
                               rtol=2e-5, atol=2e-5)


def test_dense_fallback_memory_gate(monkeypatch):
    """A measured prefer-XLA preference must not route LONG sequences
    to the dense fallback: the (B, H, Sq, Sk) f32 score tensor grows
    quadratically (48G HBM at s=8192 in the round-4 window) while the
    flash kernel is O(S).  Past the element budget the preference is
    ignored; under it the measured choice stands."""
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops import attention as A

    routed = []
    monkeypatch.setattr(
        A, "_flash",
        lambda q, *a, **k: (routed.append("flash"), q * 0)[1])
    monkeypatch.setattr(
        A, "attention_ref",
        lambda q, *a, **k: (routed.append("dense"), q * 0)[1])
    monkeypatch.setattr(_dispatch, "_PREFS",
                        {"attention": False, "attention_f32": False})

    small = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
    A.flash_attention(small, small, small, causal=True)
    assert routed == ["dense"]          # measured preference honored

    big = jnp.zeros((1, 1, 16384, 64), jnp.bfloat16)
    routed.clear()
    A.flash_attention(big, big, big, causal=True)
    assert routed == ["flash"]          # 16384^2 >= budget: gate wins

    # budget is operator-tunable; shrinking it flips the small shape
    monkeypatch.setenv("APEX_TPU_ATTN_DENSE_MAX_SCORES", "1024")
    routed.clear()
    A.flash_attention(small, small, small, causal=True)
    assert routed == ["flash"]
    monkeypatch.delenv("APEX_TPU_ATTN_DENSE_MAX_SCORES")

    # operator overrides are NOT subject to the gate: the global escape
    # hatch and an explicit PREFER_XLA must reach the dense path even at
    # shapes the gate would veto (jvp-over-custom_vjp, miscompile
    # workarounds — the operator knows why they asked)
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    routed.clear()
    A.flash_attention(big, big, big, causal=True)
    assert routed == ["dense"]
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS")
    monkeypatch.setenv("APEX_TPU_PREFER_XLA", "attention")
    routed.clear()
    A.flash_attention(big, big, big, causal=True)
    assert routed == ["dense"]


def test_attn_block_cap_measured_table(monkeypatch):
    """The sweep-written attn_block_cap table in dispatch_prefs.json
    sets the default geometry per padded head dim; the env knob still
    wins over it, and unmeasured head dims keep the static default."""
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops import attention as A

    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP", raising=False)
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {"128": 256})
    q = jnp.zeros((1, 1, 1024, 64), jnp.float32)   # dp=128
    k = jnp.zeros((1, 1, 1024, 64), jnp.float32)
    assert A._geom(q, k)[6] == 256                 # measured wins
    monkeypatch.setenv("APEX_TPU_ATTN_BLOCK_CAP", "128")
    assert A._geom(q, k)[6] == 128                 # env beats measured
    monkeypatch.delenv("APEX_TPU_ATTN_BLOCK_CAP")
    q = jnp.zeros((1, 1, 1024, 256), jnp.float32)  # dp=256: unmeasured
    k = jnp.zeros((1, 1, 1024, 256), jnp.float32)
    assert A._geom(q, k)[6] == 256                 # static default
    # a hand-edited cap above the sweep grid's ceiling for this head
    # dim is clamped to VMEM-feasible geometry, not compiled blindly
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", {"256": 1024})
    assert A._geom(q, k)[6] == 512                 # ceiling at dp=256


def test_dispatch_prefs_attn_caps_parse(tmp_path, monkeypatch):
    """_load_prefs returns the measured cap table and never propagates
    a malformed file (the documented import-safety contract)."""
    import json as _json

    from apex_tpu.ops import _dispatch

    p = tmp_path / "prefs.json"
    p.write_text(_json.dumps({
        "prefer_pallas": {"attention": True},
        "methodology": "amortized",
        "attn_block_cap": {"128": 256, "256": "512", "64": "auto",
                           "bad": 100, "worse": -128}}))
    monkeypatch.setattr(_dispatch, "_PREFS_PATH", str(p))
    prefs, caps = _dispatch._load_prefs()
    assert prefs == {"attention": True}
    # 100 is not a 128-multiple, -128 is negative, "auto" is not an
    # int: each dropped per-entry WITHOUT discarding prefer_pallas
    assert caps == {"128": 256, "256": 512}

    # a table without the amortized-methodology stamp is provisional
    # (pre-amortization runs timed the dispatch, not the kernels —
    # routing AND cap winners alike were drawn from noise): the whole
    # table is inert until a re-measure stamps it
    p.write_text(_json.dumps({
        "prefer_pallas": {"attention": False},
        "attn_block_cap": {"128": 256}}))
    assert _dispatch._load_prefs() == ({}, {})

    p.write_text("{truncated")
    assert _dispatch._load_prefs() == ({}, {})


def test_f32_attention_is_its_own_dispatch_family(monkeypatch):
    """A hardware measurement that routes f32 flash to the XLA path
    (Precision.HIGHEST multi-pass dots may lose there) must NOT take
    the bf16 kernel down with it — and vice versa."""
    from apex_tpu.ops import _dispatch, attention as A

    monkeypatch.setattr(_dispatch, "_PREFS", {"attention_f32": False})
    ks = jax.random.split(jax.random.key(0), 3)
    qf, kf, vf = (jax.random.normal(kk, (1, 2, 256, 64)) for kk in ks)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (qf, kf, vf))

    def prims(jx):
        out = set()
        def walk(j):
            for e in j.eqns:
                out.add(e.primitive.name)
                for p in e.params.values():
                    if hasattr(p, "jaxpr"):
                        walk(p.jaxpr)
        walk(jx.jaxpr)
        return out

    # recursive walk is load-bearing: a pallas_call only ever appears
    # nested inside the kernel's custom_vjp_call, never at top level
    jx32 = jax.make_jaxpr(
        lambda q, k, v: A.flash_attention(q, k, v, causal=True))(
        qf, kf, vf)
    assert "pallas_call" not in prims(jx32)

    jx16 = jax.make_jaxpr(
        lambda q, k, v: A.flash_attention(q, k, v, causal=True))(
        qb, kb, vb)
    assert "pallas_call" in prims(jx16)
    # f32 output stays correct through the rerouted path
    got = A.flash_attention(qf, kf, vf, causal=True)
    want = A.attention_ref(qf, kf, vf, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
