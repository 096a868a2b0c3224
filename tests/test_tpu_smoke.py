"""TPU smoke suite: every Pallas kernel under a REAL Mosaic compile.

The CPU tests run the kernels with ``interpret=True``; this suite runs
each kernel non-interpreted on the device against its jnp reference,
across the bench-relevant shapes.  (``chip_smoke.py`` at the repo root
is the quick proof at the main path's real widths; this is the broad
kernel matrix.)

Run on the chip:  APEX_TPU_SMOKE=1 python -m pytest tests/test_tpu_smoke.py -v
(every test skips when the backend is not a real TPU; the default
``pytest tests/`` run pins CPU in conftest, so they skip there).

Reference test model: tests/L0 oracle pattern (SURVEY.md §4) — fused
kernel vs stock implementation, allclose under per-dtype tolerances.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

@pytest.fixture(autouse=True, scope="module")
def _require_tpu():
    """Decided when the first test of this file RUNS, never at import:
    every xdist worker imports every test file, and a module that
    touches a backend while being collected gives the workers
    different tests."""
    if os.environ.get("APEX_TPU_SMOKE") != "1" \
            or jax.default_backend() != "tpu":
        pytest.skip("requires APEX_TPU_SMOKE=1 and a real TPU backend")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _close(a, b, dtype=None, **kw):
    dtype = dtype or a.dtype
    tol = {**_tol(dtype), **kw}
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 4, 512, 64), (1, 2, 2048, 128)])
def test_flash_attention_fwd(shape, causal, dtype):
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)
    o = jax.jit(flash_attention, static_argnums=(3,))(q, k, v, causal)
    o_ref = attention_ref(q, k, v, causal=causal)
    _close(o, o_ref, dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_long_seq(causal):
    """sk >= 8k must stay in the kernel (VERDICT Weak #3)."""
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = 1, 1, 8192, 128
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    o = jax.jit(flash_attention, static_argnums=(3,))(q, k, v, causal)
    _close(o, attention_ref(q, k, v, causal=causal), jnp.bfloat16)


def test_flash_attention_long_seq_grads():
    """Pallas backward kernels at multi-block length (dq over KV grid,
    dk/dv over Q grid)."""
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = 1, 2, 4096, 64
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True)
                                .astype(jnp.float32) ** 2) / s,
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(attention_ref(q, k, v, causal=True)
                                .astype(jnp.float32) ** 2) / s,
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, jnp.bfloat16, rtol=5e-2, atol=5e-2)


def test_flash_attention_segment_ids_tpu():
    """Segment masking (fmha path) under real Mosaic."""
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = 1, 2, 512, 64
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    seg = (jnp.arange(s)[None] // 128).astype(jnp.int32)
    o = jax.jit(lambda *a: flash_attention(
        *a, segment_ids=(seg, seg)))(q, k, v)
    same = seg[:, None, :, None] == seg[:, None, None, :]
    o_ref = attention_ref(q, k, v, mask=jnp.where(same, 0.0, -1e30))
    _close(o, o_ref, jnp.bfloat16)


def test_flash_attention_grads():
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = 2, 2, 256, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, True) ** 2)

    g = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_ref(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, jnp.float32, rtol=1e-3, atol=1e-3)


def test_flash_attention_gqa_grads():
    """Grouped-query attention under real Mosaic: the kernel reads the
    small K/V directly; fwd and all grads vs the repeat-kv oracle."""
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, hk, s, d = 1, 4, 2, 256, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hk, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hk, s, d), jnp.float32)

    o = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    _close(o, attention_ref(q, k, v, causal=True), jnp.float32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, True) ** 2)

    g = jax.jit(jax.grad(loss(flash_attention),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(attention_ref), argnums=(0, 1, 2))(q, k, v)
    assert g[1].shape == (b, hk, s, d)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, jnp.float32, rtol=1e-3, atol=1e-3)


def test_flash_attention_dropout_grads():
    """Fused hash-mask dropout under real Mosaic: kernel vs the jnp
    oracle sharing the same mask — fwd and all grads elementwise."""
    from apex_tpu.ops.attention import flash_attention, attention_ref
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    seed = jnp.int32(99)
    kw = dict(causal=True, dropout_rate=0.2, dropout_seed=seed)

    o = jax.jit(lambda *a: flash_attention(*a, **kw))(q, k, v)
    _close(o, attention_ref(q, k, v, **kw), jnp.float32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, **kw) ** 2)

    g = jax.jit(jax.grad(loss(flash_attention),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(attention_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, jnp.float32, rtol=1e-3, atol=1e-3)


def test_flash_attention_gqa_dropout_segments_grads():
    """The triple composition (grouped kv heads + fused dropout +
    packed-segment masking) non-interpreted on the chip — each feature
    changes the kernel's index maps, so their interaction is its own
    Mosaic surface.  Fwd + all grads vs the oracle."""
    from apex_tpu.ops.attention import attention_ref, flash_attention
    b, h, hk, s, d = 1, 4, 2, 256, 64
    ks = jax.random.split(jax.random.key(23), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hk, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hk, s, d), jnp.float32)
    ids = jnp.asarray(np.repeat([1, 2], [120, 136])[None, :],
                      jnp.int32)
    kw = dict(causal=True, dropout_rate=0.25,
              dropout_seed=jnp.int32(77))
    same = ids[:, None, :, None] == ids[:, None, None, :]
    mask = jnp.where(same, 0.0, -1e30)

    o = jax.jit(lambda *a: flash_attention(
        *a, segment_ids=(ids, ids), **kw))(q, k, v)
    _close(o, attention_ref(q, k, v, mask=mask, **kw), jnp.float32)

    g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, segment_ids=(ids, ids), **kw) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(attention_ref(
            q, k, v, mask=mask, **kw) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert g[1].shape == (b, hk, s, d)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, jnp.float32, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# layer norm / rms norm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("h", [1024, 4096])
@pytest.mark.parametrize("rms", [False, True])
def test_norm_fwd_bwd(h, rms, dtype):
    from apex_tpu.ops import layer_norm as ln
    rows = 512
    x = jax.random.normal(jax.random.key(0), (rows, h), dtype)
    w = jax.random.normal(jax.random.key(1), (h,), dtype) * 0.1 + 1.0
    b = jax.random.normal(jax.random.key(2), (h,), dtype) * 0.1

    if rms:
        fused = lambda x, w: ln.fused_rms_norm(x, w)
        ref = lambda x, w: ln.rms_norm_ref(x, w)
        args = (x, w)
    else:
        fused = lambda x, w, b: ln.fused_layer_norm(x, w, b)
        ref = lambda x, w, b: ln.layer_norm_ref(x, w, b)
        args = (x, w, b)

    y = jax.jit(fused)(*args)
    _close(y, ref(*args), dtype)

    g = jax.jit(jax.grad(lambda *a: jnp.sum(fused(*a) ** 2),
                         argnums=tuple(range(len(args)))))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                     argnums=tuple(range(len(args))))(*args)
    for a, b_ in zip(g, g_ref):
        _close(a, b_, dtype, rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# fused softmax family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_scaled_masked_softmax(dtype):
    from apex_tpu.ops import softmax as sm
    b, h, sq, sk = 2, 4, 256, 256
    x = jax.random.normal(jax.random.key(0), (b, h, sq, sk), dtype)
    mask = jax.random.bernoulli(jax.random.key(1), 0.2, (b, 1, sq, sk))
    # scale is a nondiff/static arg — jitting it traced is a TypeError
    y = jax.jit(sm.scaled_masked_softmax,
                static_argnums=(2,))(x, mask, 0.83)
    _close(y, sm.scaled_masked_softmax_ref(x, mask, 0.83), dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_scaled_upper_triang_masked_softmax(dtype):
    from apex_tpu.ops import softmax as sm
    a, sq = 8, 512
    x = jax.random.normal(jax.random.key(0), (a, sq, sq), dtype)
    y = jax.jit(sm.scaled_upper_triang_masked_softmax,
                static_argnums=(1,))(x, 0.5)
    _close(y, sm.scaled_upper_triang_masked_softmax_ref(x, 0.5), dtype)
    g = jax.jit(jax.grad(
        lambda x: jnp.sum(
            sm.scaled_upper_triang_masked_softmax(x, 0.5) ** 2)))(x)
    g_ref = jax.grad(
        lambda x: jnp.sum(
            sm.scaled_upper_triang_masked_softmax_ref(x, 0.5) ** 2))(x)
    _close(g, g_ref, dtype, rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
           atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# multi-tensor substrate (flat buffer kernels)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1 << 16, (1 << 20) + 123])
def test_flat_scale_axpby_l2norm(n):
    from apex_tpu.ops import multi_tensor as mt
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    y = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    s = jnp.float32(0.37)
    o, flag = jax.jit(mt.flat_scale)(x, s)
    o_ref, flag_ref = mt.flat_scale_ref(x, s)
    _close(o, o_ref, jnp.float32)
    assert int(flag) == int(flag_ref) == 0
    o, flag = jax.jit(mt.flat_axpby)(0.5, x, -0.25, y)
    o_ref, _ = mt.flat_axpby_ref(0.5, x, -0.25, y)
    _close(o, o_ref, jnp.float32)
    nrm = jax.jit(mt.flat_l2norm)(x)
    _close(nrm, mt.flat_l2norm_ref(x), jnp.float32, rtol=1e-4, atol=1e-4)


def test_flat_scale_inf_flag():
    from apex_tpu.ops import multi_tensor as mt
    x = jnp.array([1.0, jnp.inf, 3.0] + [0.0] * 1021, jnp.float32)
    _, flag = jax.jit(mt.flat_scale)(x, jnp.float32(1.0))
    assert int(flag) == 1


# ---------------------------------------------------------------------------
# welford / xentropy
# ---------------------------------------------------------------------------

def test_welford():
    from apex_tpu.ops import welford as wf
    x = jax.random.normal(jax.random.key(0), (4096, 256), jnp.float32) * 3
    cnt, mean, m2 = jax.jit(wf.welford_mean_var)(x)
    cnt_r, mean_r, m2_r = wf.welford_mean_var_ref(x)
    _close(mean, mean_r, jnp.float32, rtol=1e-4, atol=1e-4)
    _close(m2, m2_r, jnp.float32, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_xentropy(dtype, smoothing):
    from apex_tpu.ops import xentropy as xe
    rows, c = 1024, 32768  # BERT-vocab scale
    logits = jax.random.normal(jax.random.key(0), (rows, c), dtype)
    labels = jax.random.randint(jax.random.key(1), (rows,), 0, c)
    loss = jax.jit(lambda l, t: xe.softmax_cross_entropy(
        l, t, smoothing=smoothing))(logits, labels)
    loss_ref = xe.softmax_cross_entropy_ref(logits, labels,
                                            smoothing=smoothing)
    _close(loss, loss_ref, dtype)
    g = jax.jit(jax.grad(lambda l: jnp.sum(
        xe.softmax_cross_entropy(l, labels, smoothing=smoothing))))(logits)
    g_ref = jax.grad(lambda l: jnp.sum(
        xe.softmax_cross_entropy_ref(l, labels,
                                     smoothing=smoothing)))(logits)
    _close(g, g_ref, dtype, rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
           atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# rope / transducer / wgrad (jnp+scan paths — compile-on-TPU sanity)
# ---------------------------------------------------------------------------

def test_rope():
    from apex_tpu.ops import rope as rp
    s, b, h, d = 256, 2, 4, 64
    t = jax.random.normal(jax.random.key(0), (s, b, h, d), jnp.bfloat16)
    freqs = jax.random.normal(jax.random.key(1), (s, 1, 1, d), jnp.float32)
    y = jax.jit(rp.fused_apply_rotary_pos_emb)(t, freqs)
    _close(y, rp.rope_ref(t, freqs), jnp.bfloat16)


def test_transducer_loss():
    from apex_tpu.ops import transducer as td
    b, t, u, v = 2, 16, 8, 32
    x = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(0), (b, t, u + 1, v)), axis=-1)
    label = jax.random.randint(jax.random.key(1), (b, u), 1, v)
    f_len = jnp.array([t, t - 3])
    y_len = jnp.array([u, u - 2])
    loss = jax.jit(td.transducer_loss)(x, label, f_len, y_len)
    loss_ref = td.transducer_loss_ref(x, label, f_len, y_len)
    _close(loss, loss_ref, jnp.float32, rtol=1e-4, atol=1e-4)


def test_wgrad_accum():
    from apex_tpu.ops import wgrad as wg
    x = jax.random.normal(jax.random.key(0), (512, 1024), jnp.bfloat16)
    dy = jax.random.normal(jax.random.key(1), (512, 2048), jnp.bfloat16)
    main = jnp.zeros((2048, 1024), jnp.float32)
    out = jax.jit(wg.wgrad_gemm_accum_fp32)(x, dy, main)
    ref = wg.wgrad_gemm_accum_ref(x, dy, main)
    _close(out, ref, jnp.float32, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# round-2 additions: int8 MXU matmuls, host-offload paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dynamic", [False, True])
def test_int8_matmul(dynamic):
    from apex_tpu.quantization import int8_matmul, quantize_int8
    x = jax.random.normal(jax.random.key(0), (128, 512), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (512, 256)) * 0.1
    y = jax.jit(lambda x: int8_matmul(x, quantize_int8(w),
                                      dynamic=dynamic))(x)
    y_ref = x.astype(jnp.float32) @ w
    _close(y, y_ref, jnp.bfloat16, rtol=0.08, atol=0.15)


def test_offloaded_optimizer_fused_step():
    """offload_state on REAL hardware: state in pinned host memory,
    one-program step, numerics equal to the resident optimizer."""
    from apex_tpu.optimizers import FusedAdam
    params = {"w": jax.random.normal(jax.random.key(0), (1 << 16,))}
    g = {"w": jax.random.normal(jax.random.key(1), (1 << 16,)) * 0.01}
    ref = FusedAdam(params, lr=1e-3)
    off = FusedAdam(params, lr=1e-3, offload_state=True)
    assert off._fused_offload          # on TPU the fused path is built
    for _ in range(3):
        ref.step(g)
        off.step(g)
    _close(off.params["w"], ref.params["w"], jnp.float32,
           rtol=1e-6, atol=1e-6)
    for leaf in jax.tree_util.tree_leaves(off.opt_state):
        assert leaf.sharding.memory_kind == "pinned_host"


def test_activation_offload_grads():
    from apex_tpu.offload import checkpoint_name, offload_checkpoint
    w1 = jax.random.normal(jax.random.key(0), (256, 1024),
                           jnp.bfloat16) * 0.05
    w2 = jax.random.normal(jax.random.key(1), (1024, 256),
                           jnp.bfloat16) * 0.05
    x = jax.random.normal(jax.random.key(2), (512, 256), jnp.bfloat16)

    def block(w1, w2, x):
        h = checkpoint_name(jax.nn.gelu(
            jnp.dot(x, w1, preferred_element_type=jnp.float32)
            .astype(jnp.bfloat16)), "ffn_hidden")
        return jnp.dot(h, w2, preferred_element_type=jnp.float32)

    def loss(f):
        return lambda w1, w2, x: jnp.sum(f(w1, w2, x) ** 2)

    off = offload_checkpoint(block, offload_names=("ffn_hidden",))
    g_off = jax.jit(jax.grad(loss(off), argnums=(0, 1)))(w1, w2, x)
    g_ref = jax.jit(jax.grad(loss(block), argnums=(0, 1)))(w1, w2, x)
    # The terminal forces --xla_allow_excess_precision=true, under
    # which the UNrematerialized program may keep the f32 gelu output
    # where it only feeds a dot, while the offloaded program rounds h
    # through bf16 at the host boundary (round-4 window: every diff
    # was <= 1 bf16 ulp of the row scale; the fixed atol=0.02 flagged
    # near-zero elements).  Compare up to one bf16 rounding of each
    # ROW's dominant term — global-max scaling would grant large-row
    # slack to small rows and hide a real offload bug there.
    for a, b in zip(g_off, g_ref):
        a32 = np.asarray(a, np.float32)
        b32 = np.asarray(b, np.float32)
        row = np.max(np.abs(b32), axis=-1, keepdims=True)
        tol = 2.0 ** -7 * row + 0.02 * np.abs(b32) + 1e-6
        bad = np.abs(a32 - b32) > tol
        assert not bad.any(), (
            f"{bad.sum()} elements beyond row-scaled bf16 tolerance; "
            f"max diff {np.max(np.abs(a32 - b32)):.4g}")
