"""AOT TPU lowering of every Pallas kernel — no TPU needed.

``jax.jit(f).trace(args).lower(lowering_platforms=("tpu",))`` runs the
Mosaic kernel serializer and its verifier on a CPU host.  This catches
the class of bug the round-2 hardware run surfaced (e.g. "Can only
store scalars to SMEM" in the Welford kernel — interpret mode accepts
it, Mosaic rejects it) **in CPU CI**, without claiming the single-client
TPU.  It does not replace tests/test_tpu_smoke.py (the backend
compile + numerics still need hardware); it front-runs it.

APEX_TPU_FORCE_MOSAIC=1 makes ops/_dispatch emit non-interpreted
pallas_calls off-TPU so the lowering actually contains Mosaic kernels.
"""

import functools

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True)
def _force_mosaic(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FORCE_MOSAIC", "1")


def lower_tpu(f, *args, static=()):
    jax.jit(f, static_argnums=static).trace(*args).lower(
        lowering_platforms=("tpu",))


def grad_of(f, n):
    return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                    argnums=tuple(range(n)))


# --------------------------------------------------------------------------
# attention family
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("causal", [False, True])
def test_lower_flash_attention(causal, dtype):
    from apex_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 1024, 64), dtype)
    f = functools.partial(flash_attention, causal=causal)
    lower_tpu(lambda q: f(q, q, q), q)
    lower_tpu(grad_of(lambda q: f(q, q, q), 1), q)


def test_lower_flash_attention_segments_and_longseq():
    from apex_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 1, 512, 64), jnp.bfloat16)
    seg = (jnp.zeros((1, 512), jnp.int32),) * 2
    lower_tpu(lambda q: flash_attention(q, q, q, segment_ids=seg), q)
    ql = jnp.zeros((1, 1, 8192, 128), jnp.bfloat16)
    lower_tpu(lambda q: flash_attention(q, q, q, True), ql)
    lower_tpu(grad_of(lambda q: flash_attention(q, q, q, True), 1), ql)


# --------------------------------------------------------------------------
# norm / softmax / xentropy / welford / wgrad
# --------------------------------------------------------------------------

def test_lower_flash_attention_dropout():
    """Fused hash-mask dropout (SMEM seed scalar + int vector hash in
    every kernel) must pass the Mosaic verifier, fwd and bwd."""
    from apex_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 1024, 64), jnp.bfloat16)
    s = jnp.int32(7)

    def f(q, s):
        return flash_attention(q, q, q, True, dropout_rate=0.1,
                               dropout_seed=s)
    lower_tpu(f, q, s)
    lower_tpu(grad_of(lambda q, s: f(q, s), 1), q, s)


def test_lower_flash_attention_single_kv_block():
    """nk == 1 geometry takes the dedicated scratch-free fast-path
    body (_fwd_kernel_1kv) — its own Mosaic lowering, every variant:
    ± causal, ± lse (inference), fused dropout."""
    import functools

    from apex_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 512, 64), jnp.bfloat16)
    for causal in (False, True):
        f = functools.partial(flash_attention, causal=causal)
        lower_tpu(lambda q, f=f: f(q, q, q), q)            # no-lse fwd
        lower_tpu(grad_of(lambda q, f=f: f(q, q, q), 1), q)  # lse fwd
    s = jnp.int32(3)
    lower_tpu(lambda q, s: flash_attention(
        q, q, q, True, dropout_rate=0.1, dropout_seed=s), q, s)


def test_lower_flash_attention_gqa():
    """GQA/MQA geometry (kv rows indexed through _kv_row, dkv grid
    folding the q group into its sequential axis) must pass the Mosaic
    verifier, fwd and bwd."""
    from apex_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 8, 1024, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 2, 1024, 64), jnp.bfloat16)
    lower_tpu(lambda q, k, v: flash_attention(q, k, v, True), q, kv, kv)
    lower_tpu(grad_of(
        lambda q, k, v: flash_attention(q, k, v, True), 3), q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("rms", [False, True])
def test_lower_norms(rms, dtype):
    from apex_tpu.ops import layer_norm as ln
    x = jnp.zeros((512, 1024), dtype)
    w = jnp.ones((1024,), dtype)
    b = jnp.zeros((1024,), dtype)
    if rms:
        lower_tpu(ln.fused_rms_norm, x, w)
        lower_tpu(grad_of(ln.fused_rms_norm, 2), x, w)
    else:
        lower_tpu(ln.fused_layer_norm, x, w, b)
        lower_tpu(grad_of(ln.fused_layer_norm, 3), x, w, b)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_lower_softmax_family(dtype):
    from apex_tpu.ops import softmax as sm
    x = jnp.zeros((2, 4, 256, 256), dtype)
    mask = jnp.zeros((2, 1, 256, 256), bool)
    lower_tpu(sm.scaled_masked_softmax, x, mask, 0.83, static=(2,))
    xt = jnp.zeros((8, 512, 512), dtype)
    lower_tpu(sm.scaled_upper_triang_masked_softmax, xt, 0.5, static=(1,))
    lower_tpu(grad_of(
        lambda t: sm.scaled_upper_triang_masked_softmax(t, 0.5), 1), xt)


def test_softmax_traced_scale_raises_clearly():
    """jitting the raw op with a traced scale must fail with guidance,
    not an opaque UnexpectedTracerError from custom_vjp internals (the
    round-2 TPU smoke failure mode)."""
    from apex_tpu.ops import softmax as sm
    x = jnp.zeros((2, 4, 256, 256), jnp.float32)
    mask = jnp.zeros((2, 1, 256, 256), bool)
    with pytest.raises(TypeError, match="static_argnums"):
        jax.jit(sm.scaled_masked_softmax)(x, mask, 0.83)
    with pytest.raises(TypeError, match="static_argnums"):
        jax.jit(sm.scaled_upper_triang_masked_softmax)(
            jnp.zeros((8, 128, 128)), 0.5)


def test_lower_xentropy_welford_wgrad():
    from apex_tpu.ops import welford as wf
    from apex_tpu.ops import wgrad as wg
    from apex_tpu.ops import xentropy as xe
    logits = jnp.zeros((1024, 32768), jnp.bfloat16)
    labels = jnp.zeros((1024,), jnp.int32)
    lower_tpu(lambda l: xe.softmax_cross_entropy(l, labels,
                                                 smoothing=0.1), logits)
    lower_tpu(grad_of(lambda l: xe.softmax_cross_entropy(
        l, labels, smoothing=0.1), 1), logits)
    lower_tpu(wf.welford_mean_var, jnp.zeros((4096, 256)))
    lower_tpu(wg.wgrad_gemm_accum_fp32,
              jnp.zeros((512, 1024), jnp.bfloat16),
              jnp.zeros((512, 2048), jnp.bfloat16),
              jnp.zeros((2048, 1024), jnp.float32))


# --------------------------------------------------------------------------
# multi-tensor substrate
# --------------------------------------------------------------------------

def test_lower_multi_tensor_family():
    from apex_tpu.ops import multi_tensor as mt
    n = (1 << 20) + 123
    p = jnp.zeros((n,), jnp.float32)
    lower_tpu(mt.flat_scale, p, jnp.float32(0.5))
    lower_tpu(lambda x, y: mt.flat_axpby(0.5, x, -0.25, y), p, p)
    lower_tpu(mt.flat_l2norm, p)
    lower_tpu(lambda a, g: mt.flat_accumulate(a, g, 0.5), p,
              p.astype(jnp.bfloat16))
    lower_tpu(lambda g: mt.flat_unscale_norm(g, 1 / 128.0),
              p.astype(jnp.bfloat16))
