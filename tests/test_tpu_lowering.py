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


# --------------------------------------------------------------------------
# KDA: each kernel lowered once a program
# --------------------------------------------------------------------------

def test_four_kda_layers_lower_each_kernel_once(monkeypatch):
    """The gradient of four distinct checkpointed layers around
    ``chunk_kda``: ``apex_kda_fwd`` lowered at most twice (the primal
    forward, which ``jax.checkpoint`` traces apart, and the custom VJP's
    forward rule),
    ``apex_kda_bwd`` once, every kernel under the ``apex_kda`` scope, and
    the process account's ``kernel_traces`` the number of kernels
    lowered: one trace a lowering, where a ``pallas_call`` traced at each
    site would be twelve."""
    import re
    from apex_tpu.ops import kda
    from apex_tpu.telemetry import retrace

    account = retrace.ProcessAccount()
    account.open = True
    monkeypatch.setattr(retrace, "_PROCESS", account)
    jax.clear_caches()

    def layer(i):
        def f(x, w):
            q = kda.l2_normalize(jnp.tanh(x * w[0] + i))
            k = kda.l2_normalize(jnp.tanh(x * w[1] - i))
            g = -jax.nn.softplus(x * w[3])
            beta = jax.nn.sigmoid(x[..., 0] * w[4, 0])
            return x + kda.chunk_kda(q, k, (x * w[2]).astype(jnp.bfloat16),
                                     g, beta).astype(x.dtype)
        return jax.checkpoint(f)

    def loss(x, ws):
        for i in range(4):
            x = layer(i)(x, ws[i])
        return jnp.sum(x ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        jnp.zeros((1, 8, 256, 128)), jnp.zeros((4, 5, 128))).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*"
                       r"kernel_name = \"(\w+)\".*loc\((#loc\d+)\)$",
                       text, re.M)
    names = [name for name, _ in calls]
    assert names.count("apex_kda_fwd") in (1, 2), names
    assert names.count("apex_kda_bwd") == 1, names
    assert all("apex_kda/scan/" in locs[loc] for _, loc in calls), \
        [locs[loc] for _, loc in calls]
    assert account.summary()["kernel_traces"] == len(names) <= 3


# --------------------------------------------------------------------------
# SSD: one forward and one backward kernel program a step
# --------------------------------------------------------------------------

def test_three_mamba_layers_trace_one_forward_and_one_backward_kernel(
        monkeypatch):
    """The gradient of three distinct checkpointed layers around
    ``chunk_ssd`` at the cell's widths (64 heads of 64, 8 groups of a
    state of 128): the process account's ``kernel_traces`` is 2, one
    forward and one backward kernel program for the whole step (the
    primal and the custom VJP's forward rule share one trace), lowered
    where jax places them: the forward pass's, the rematerialised
    forward's and the backward's, each under the ``apex_ssd/scan``
    scope."""
    import re
    from apex_tpu.ops import ssd
    from apex_tpu.telemetry import retrace

    account = retrace.ProcessAccount()
    account.open = True
    monkeypatch.setattr(retrace, "_PROCESS", account)
    jax.clear_caches()
    s, h, p, g, n = 256, 64, 64, 8, 128

    def layer(i):
        def f(x, w):
            xs = (x * w[0]).reshape(1, s, h, p).astype(jnp.bfloat16)
            dt = jax.nn.softplus(x[..., :h] * w[1, :h] + i)
            B = jnp.tanh(x[..., :g * n] * w[2, :g * n]).reshape(1, s, g, n)
            C = jnp.tanh(x[..., :g * n] * w[3, :g * n]).reshape(1, s, g, n)
            y = ssd.chunk_ssd(xs, dt, -jnp.arange(1.0, h + 1),
                              B.astype(jnp.bfloat16), C.astype(jnp.bfloat16))
            return x + y.reshape(1, s, h * p).astype(x.dtype)
        return jax.checkpoint(f)

    def loss(x, ws):
        for i in range(3):
            x = layer(i)(x, ws[i])
        return jnp.sum(x ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        jnp.zeros((1, s, h * p)), jnp.zeros((3, 4, h * p))).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*"
                       r"kernel_name = \"(\w+)\".*loc\((#loc\d+)\)$",
                       text, re.M)
    names = [name for name, _ in calls]
    assert account.summary()["kernel_traces"] == 2
    assert names.count("apex_ssd_fwd") in (1, 2), names
    assert names.count("apex_ssd_bwd") == 1, names
    assert all("apex_ssd/scan/" in locs[loc] for _, loc in calls), \
        [locs[loc] for _, loc in calls]
