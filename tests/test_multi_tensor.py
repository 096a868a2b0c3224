"""Pallas multi-tensor kernels vs jnp oracles, and the flat optimizer
updates (``jnp``, no kernel) vs the per-leaf math and torch.

Mirrors the reference's dominant test pattern (SURVEY.md §4): fused kernel
vs stock oracle, allclose under per-dtype tolerances, over a small
shape x dtype grid.  Kernels run in interpreter mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import multi_tensor as mt
from apex_tpu.multi_tensor_apply import (flatten, unflatten,
                                         multi_tensor_applier)
from apex_tpu.optimizers import _functional as F

SIZES = [1, 100, 128, 1024, 5000]
DTYPES = [jnp.float32, jnp.bfloat16]


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-6, atol=1e-6)


def per_leaf(leaf_step, sizes, bufs, **kw):
    """The independent oracle of a flat update: ``leaf_step`` (one of
    ``optimizers/_functional``'s) over each segment of the buffers on
    its own, the results laid end to end again."""
    bounds = np.cumsum((0,) + tuple(sizes))
    outs = [leaf_step(*(b[lo:hi] for b in bufs), **kw)
            for lo, hi in zip(bounds[:-1], bounds[1:])]
    return tuple(jnp.concatenate(o) for o in zip(*outs))


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_scale(n, dtype):
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32).astype(dtype)
    out, flag = mt.flat_scale(x, 2.5)
    ref, rflag = mt.flat_scale_ref(x, 2.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))
    assert int(flag) == int(rflag) == 0


def test_flat_scale_detects_inf():
    x = jnp.array([1.0, jnp.inf, 3.0], jnp.float32)
    _, flag = mt.flat_scale(x, 1.0)
    assert int(flag) == 1
    x = jnp.array([1.0, jnp.nan], jnp.float32)
    _, flag = mt.flat_scale(x, 1.0)
    assert int(flag) == 1


@pytest.mark.parametrize("n", SIZES)
def test_flat_axpby(n):
    k1, k2 = jax.random.split(jax.random.key(1))
    x = jax.random.normal(k1, (n,))
    y = jax.random.normal(k2, (n,))
    out, flag = mt.flat_axpby(0.5, x, -1.5, y)
    ref, _ = mt.flat_axpby_ref(0.5, x, -1.5, y)
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    assert int(flag) == 0


@pytest.mark.parametrize("n", SIZES)
def test_flat_l2norm(n):
    x = jax.random.normal(jax.random.key(2), (n,))
    got = mt.flat_l2norm(x)
    want = mt.flat_l2norm_ref(x)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("adam_w", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_adam_matches_per_leaf(adam_w, dtype):
    sizes = (257, 128, 1000, 5, 1610)
    n = sum(sizes)
    keys = jax.random.split(jax.random.key(3), 4)
    p = jax.random.normal(keys[0], (n,), jnp.float32).astype(dtype)
    g = jax.random.normal(keys[1], (n,), jnp.float32).astype(dtype)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
              weight_decay=0.01, step=1, adam_w_mode=adam_w)
    po, mo, vo = mt.flat_adam(p, g, m, v, **kw)
    pr, mr, vr = per_leaf(F.adam_step, sizes, (p, g, m, v), **kw)
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pr, np.float32), **tol(dtype))
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(vo, vr, rtol=1e-5, atol=1e-6)


def test_flat_adam_matches_torch_adamw():
    torch = pytest.importorskip("torch")
    n = 512
    rng = np.random.RandomState(0)
    p0 = rng.randn(n).astype(np.float32)
    g0 = rng.randn(n).astype(np.float32)

    tp = torch.nn.Parameter(torch.tensor(p0))
    opt = torch.optim.AdamW([tp], lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                            weight_decay=0.01)
    tp.grad = torch.tensor(g0)
    opt.step()

    p = jnp.asarray(p0)
    g = jnp.asarray(g0)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    po, _, _ = mt.flat_adam(p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999,
                            eps=1e-8, weight_decay=0.01, step=1,
                            adam_w_mode=True)
    np.testing.assert_allclose(np.asarray(po), tp.detach().numpy(),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("momentum,nesterov", [(0.0, False), (0.9, False),
                                               (0.9, True)])
def test_flat_sgd_matches_torch(momentum, nesterov):
    torch = pytest.importorskip("torch")
    n = 257
    rng = np.random.RandomState(1)
    p0 = rng.randn(n).astype(np.float32)

    tp = torch.nn.Parameter(torch.tensor(p0))
    opt = torch.optim.SGD([tp], lr=0.1, momentum=momentum,
                          nesterov=nesterov, weight_decay=1e-4)
    p = jnp.asarray(p0)
    buf = jnp.zeros((n,), jnp.float32)
    for step in range(3):
        g0 = rng.randn(n).astype(np.float32)
        tp.grad = torch.tensor(g0)
        opt.step()
        p, buf = mt.flat_sgd(p, jnp.asarray(g0), buf, lr=0.1,
                             momentum=momentum, nesterov=nesterov,
                             weight_decay=1e-4, first_run=(step == 0))
    np.testing.assert_allclose(np.asarray(p), tp.detach().numpy(),
                               rtol=1e-5, atol=1e-6)


def test_flatten_unflatten_roundtrip():
    ts = [jnp.arange(6.0).reshape(2, 3), jnp.ones((4,)), jnp.zeros((1, 1))]
    flat = flatten(ts)
    assert flat.shape == (11,)
    back = unflatten(flat, ts)
    for a, b in zip(ts, back):
        np.testing.assert_array_equal(a, b)


def test_multi_tensor_applier_scale():
    ts = [jnp.full((5,), 2.0), jnp.full((3, 3), -1.0)]
    outs, flag = multi_tensor_applier(mt.flat_scale, None, [ts], 3.0)
    np.testing.assert_allclose(outs[0], jnp.full((5,), 6.0))
    np.testing.assert_allclose(outs[1], jnp.full((3, 3), -3.0))
    assert int(flag) == 0


class TestDispatchPrefs:
    """Measure-aware dispatch (VERDICT r2 #2): the preference table and
    env overrides gate each kernel family onto Pallas or the XLA path."""

    def test_default_prefers_pallas(self, monkeypatch):
        from apex_tpu.ops import _dispatch
        monkeypatch.setattr(_dispatch, "_PREFS", {})
        monkeypatch.delenv("APEX_TPU_PREFER_XLA", raising=False)
        monkeypatch.delenv("APEX_TPU_PREFER_PALLAS", raising=False)
        assert _dispatch.op_enabled("layer_norm")
        assert _dispatch.op_enabled("never-measured-op")

    def test_measured_loss_flips_to_xla(self, monkeypatch):
        from apex_tpu.ops import _dispatch
        monkeypatch.setattr(_dispatch, "_PREFS", {"softmax": False,
                                                  "attention": True})
        assert not _dispatch.op_enabled("softmax")
        assert _dispatch.op_enabled("attention")

    def test_env_overrides_beat_table(self, monkeypatch):
        from apex_tpu.ops import _dispatch
        monkeypatch.setattr(_dispatch, "_PREFS", {"softmax": False})
        monkeypatch.setenv("APEX_TPU_PREFER_PALLAS", "softmax")
        assert _dispatch.op_enabled("softmax")
        monkeypatch.setenv("APEX_TPU_PREFER_XLA", "layer_norm, xentropy")
        assert not _dispatch.op_enabled("layer_norm")
        assert not _dispatch.op_enabled("xentropy")

    def test_disabled_pallas_wins_over_everything(self, monkeypatch):
        from apex_tpu.ops import _dispatch
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
        monkeypatch.setenv("APEX_TPU_PREFER_PALLAS", "softmax")
        assert not _dispatch.op_enabled("softmax")

    def test_xla_pref_routes_layer_norm_to_oracle(self, monkeypatch):
        """The gate actually changes the computed path: with layer_norm
        preferred to XLA, fused_layer_norm still computes correctly
        (through the reference path) and no pallas_call appears."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from apex_tpu.ops import _dispatch, layer_norm as ln

        x = jax.random.normal(jax.random.key(0), (16, 256))
        w = jnp.ones((256,)); b = jnp.zeros((256,))
        want = ln.layer_norm_ref(x, w, b)
        monkeypatch.setattr(_dispatch, "_PREFS", {"layer_norm": False})
        got = ln.fused_layer_norm(x, w, b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        jx = jax.make_jaxpr(lambda t: ln.fused_layer_norm(t, w, b))(x)
        prims = {e.primitive.name for e in jx.jaxpr.eqns}
        assert "pallas_call" not in prims, prims

    def test_prefs_written_from_rows(self, tmp_path):
        import importlib, json as _json, os as _os
        tools = _os.path.abspath(_os.path.join(
            _os.path.dirname(__file__), "..", "tools"))
        import sys as _sys
        _sys.path.insert(0, tools)
        try:
            kb = importlib.import_module("kernel_bench")
        finally:
            _sys.path.remove(tools)
        rows = [
            {"kernel": "fused_layer_norm", "speedup": 1.4, "backend": "tpu"},
            {"kernel": "fused_layer_norm_grad", "speedup": 0.8,
             "backend": "tpu"},
            {"kernel": "flash_attention", "speedup": 2.0, "backend": "tpu"},
            {"kernel": "int8_matmul_weight_only", "speedup": 1.9,
             "backend": "tpu"},               # not a dispatch family
            {"kernel": "flat_unscale_norm", "speedup": None,
             "backend": "tpu"},
        ]
        p = tmp_path / "prefs.json"
        prefs = kb.write_prefs(rows, str(p))
        data = _json.loads(p.read_text())
        # one slow shape disables the family; missing speedups ignored
        assert prefs == {"layer_norm": False, "attention": True}
        assert data["prefer_pallas"] == prefs
        assert data["methodology"] == "amortized"


@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_adagrad_matches_per_leaf(dtype):
    sizes = (257, 128, 1000, 5, 610)
    n = sum(sizes)
    keys = jax.random.split(jax.random.key(4), 2)
    p = jax.random.normal(keys[0], (n,), jnp.float32).astype(dtype)
    g = jax.random.normal(keys[1], (n,), jnp.float32).astype(dtype)
    h = jnp.abs(jax.random.normal(jax.random.key(5), (n,))) * 0.1
    kw = dict(lr=1e-2, eps=1e-10, weight_decay=0.01)
    po, ho = mt.flat_adagrad(p, g, h, **kw)
    pr, hr = per_leaf(F.adagrad_step, sizes, (p, g, h), **kw)
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pr, np.float32), **tol(dtype))
    np.testing.assert_allclose(ho, hr, rtol=1e-5, atol=1e-6)


SEGMENT_SIZES = (257, 128, 1000, 5)


def _segmented_buffers(key=6):
    n = sum(SEGMENT_SIZES)
    ks = jax.random.split(jax.random.key(key), 4)
    p = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    m = jax.random.normal(ks[2], (n,)) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (n,))) * 0.1
    return p, g, m, v, SEGMENT_SIZES


@pytest.mark.parametrize("sizes,dtype,poison", [
    ((257, 128, 1000, 5), jnp.float32, None),       # no multiple of 128
    ((3000, 1, 1024, 1, 7), jnp.float32, None),     # 1-element leaves
    ((4097,), jnp.float32, None),                   # single-leaf bucket
    ((1024, 2048, 1024), jnp.float32, None),        # tile-aligned
    ((257, 128, 1000, 5), jnp.bfloat16, None),      # f32 accumulation
    ((300, 1, 77), jnp.float32, np.nan),            # nan stays in its leaf
    ((300, 1, 77), jnp.float32, np.inf),
], ids=["ragged", "one_element", "single_leaf", "aligned", "bf16", "nan",
        "inf"])
def test_flat_segment_reductions_match_per_leaf(sizes, dtype, poison):
    """The static-boundary reductions against plain per-leaf ``jnp.sum``
    / ``jnp.max`` on slices of the same buffer, and the broadcast back
    against ``np.repeat``."""
    n = sum(sizes)
    x = (jax.random.normal(jax.random.key(11), (n,)) * 3.0).astype(dtype)
    if poison is not None:
        x = x.at[sizes[0]].set(poison)          # leaf 1's only element
    sumsq = jax.jit(lambda a: mt.flat_segment_sumsq(a, sizes))(x)
    absmax = jax.jit(lambda a: mt.flat_segment_absmax(a, sizes))(x)
    assert sumsq.shape == absmax.shape == (len(sizes),)
    assert sumsq.dtype == absmax.dtype == jnp.float32
    xf = np.asarray(x, np.float32)
    bounds = np.cumsum((0,) + tuple(sizes))
    leaves = [xf[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
    np.testing.assert_allclose(
        sumsq, [np.sum(np.square(l, dtype=np.float64)) for l in leaves],
        rtol=1e-5)
    np.testing.assert_array_equal(absmax,
                                  [np.max(np.abs(l)) for l in leaves])
    if poison is not None:
        finite = np.isfinite(np.stack([sumsq, absmax]))
        assert not finite[:, 1].any() and finite[:, (0, 2)].all()
    back = mt.flat_segment_broadcast(jnp.arange(len(sizes), dtype=jnp.float32),
                                     sizes)
    np.testing.assert_array_equal(
        back, np.repeat(np.arange(len(sizes), dtype=np.float32), sizes))


def test_flat_segment_sizes_must_cover_the_buffer():
    with pytest.raises(ValueError, match="segment sizes sum to 10"):
        mt.flat_segment_sumsq(jnp.ones((11,)), (4, 6))


@pytest.mark.parametrize("use_nvlamb", [False, True])
def test_flat_lamb_matches_per_leaf(use_nvlamb):
    p, g, m, v, sizes = _segmented_buffers()
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-6,
              weight_decay=0.01, step=3, clip_coeff=0.7,
              use_nvlamb=use_nvlamb)
    po, mo, vo = mt.flat_lamb(p, g, m, v, sizes, **kw)
    pr, mr, vr = per_leaf(F.lamb_step, sizes, (p, g, m, v), **kw)
    np.testing.assert_allclose(po, pr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(vo, vr, rtol=1e-5, atol=1e-6)


def test_flat_lamb_trust_ratio_is_per_segment():
    """The segmented update must reproduce the per-leaf trust ratios —
    not one bucket-global ratio."""
    p, g, m, v, sizes = _segmented_buffers()
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-6,
              weight_decay=0.01, step=3)
    po, _, _ = mt.flat_lamb(p, g, m, v, sizes, **kw)
    pe, _, _ = per_leaf(F.lamb_step, sizes, (p, g, m, v), **kw)
    np.testing.assert_allclose(po, pe, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("first_run", [True, False])
def test_flat_novograd_matches_per_leaf(first_run):
    p, g, m, _, sizes = _segmented_buffers(key=8)
    vseg = jnp.abs(jax.random.normal(jax.random.key(9),
                                     (len(sizes),))) * 0.2
    kw = dict(lr=1e-3, beta1=0.95, beta2=0.98, eps=1e-8,
              weight_decay=0.01, first_run=first_run)
    po, mo, vo = mt.flat_novograd(p, g, m, vseg, sizes, **kw)
    o = 0
    for i, sz in enumerate(sizes):
        sl = slice(o, o + sz)
        pe, me, ve = F.novograd_step(p[sl], g[sl], m[sl], vseg[i], **kw)
        np.testing.assert_allclose(po[sl], pe, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(mo[sl], me, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(vo[i], ve, rtol=1e-5, atol=1e-6)
        o += sz


def test_flat_sgd_traced_first_run():
    """first_run may be a traced bool (step == 1 inside a jitted
    optimizer step): the select must pick what the per-leaf math does
    with a Python bool."""
    n = 300
    p = jax.random.normal(jax.random.key(0), (n,))
    g = jax.random.normal(jax.random.key(1), (n,))
    buf = jax.random.normal(jax.random.key(2), (n,))
    kw = dict(lr=0.1, momentum=0.9, weight_decay=1e-4)

    @jax.jit
    def step(p, g, buf, count):
        return mt.flat_sgd(p, g, buf, first_run=count == 1, **kw)

    for count, want_first in ((1, True), (2, False)):
        po, bo = step(p, g, buf, jnp.int32(count))
        pr, br = F.sgd_step(p, g, buf, first_run=want_first, **kw)
        np.testing.assert_allclose(po, pr, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(bo, br, rtol=1e-6, atol=1e-7)


class TestMultiTensorApplierMixedDtype:
    """The reference dispatches per dtype group; extras (overflow flags,
    norms) combine across groups — flags by max, norms by rss."""

    def test_mixed_dtype_scale_groups_and_flags(self):
        ts = [jnp.full((5,), 2.0, jnp.float32),
              jnp.full((3, 3), -1.0, jnp.bfloat16),
              jnp.full((7,), 4.0, jnp.float32)]
        outs, flag = multi_tensor_applier(mt.flat_scale, None, [ts], 3.0)
        assert [o.dtype for o in outs] == [t.dtype for t in ts]
        np.testing.assert_allclose(np.asarray(outs[0]), 6.0)
        np.testing.assert_allclose(np.asarray(outs[1], np.float32), -3.0)
        np.testing.assert_allclose(np.asarray(outs[2]), 12.0)
        assert int(flag) == 0

    def test_mixed_dtype_flag_combines_by_max(self):
        ts = [jnp.ones((4,), jnp.float32),
              jnp.array([1.0, jnp.inf], jnp.bfloat16)]
        _, flag = multi_tensor_applier(mt.flat_scale, None, [ts], 1.0)
        assert int(flag) == 1

    def test_mixed_dtype_norm_combines_by_rss(self):
        ts = [jnp.full((4,), 3.0, jnp.float32),
              jnp.full((4,), 4.0, jnp.bfloat16)]
        (norm,) = multi_tensor_applier(mt.flat_l2norm, None, [ts])
        want = np.sqrt(sum(float(jnp.sum(t.astype(jnp.float32) ** 2))
                           for t in ts))
        np.testing.assert_allclose(float(norm), want, rtol=1e-3)
