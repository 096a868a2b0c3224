"""The grouped-matmul kernels (``ops/grouped_matmul.py``), interpreted,
against ``lax.ragged_dot``: the product and the two gradients of its
VJP over the rows in use, with every row past them poisoned with NaN;
the dropless expert layer through them against the layer through
``ragged_dot``; the metric that reads the kernels' time."""

import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import grouped_matmul as gm
from apex_tpu.transformer import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.lax.Precision.HIGHEST


def _poisoned(x, used):
    return jnp.where(jnp.arange(x.shape[0])[:, None] < used, x, jnp.nan)


def _close(got, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(np.max(np.abs(want))))


@pytest.mark.parametrize("m, k, n, counts, dtype", [
    # K and N no lane multiples, every row of the buffer in use
    (256, 96, 200, [60, 70, 50, 76], jnp.float32),
    # empty groups, first and in the middle: their weights' gradient is 0
    (256, 128, 128, [0, 100, 0, 120], jnp.float32),
    # a group across three row tiles, and boundaries inside tiles
    (512, 64, 136, [40, 300, 100, 72], jnp.float32),
    # a tenth of the buffer in use
    (512, 128, 64, [10, 20, 30, 40], jnp.float32),
    # a buffer that is no row-tile multiple (padded)
    (200, 64, 128, [50, 0, 90, 30], jnp.float32),
    # the operands' dtype out, accumulated in float32
    (384, 256, 128, [100, 100, 100, 50], jnp.bfloat16),
], ids=["k_n_not_lane_multiples", "empty_groups", "group_straddles_tiles",
        "rows_in_use_fewer", "rows_not_a_tile_multiple", "bf16"])
def test_grouped_matmul_matches_ragged_dot(m, k, n, counts, dtype):
    ks = jax.random.split(jax.random.key(m + k + n), 3)
    counts = jnp.array(counts, jnp.int32)
    used = int(counts.sum())
    lhs = jax.random.normal(ks[0], (m, k), dtype)
    rhs = jax.random.normal(ks[1], (4, k, n), dtype)
    dout = jax.random.normal(ks[2], (m, n), dtype)
    prec = moe.matmul_precision(dtype)

    def ours(a, b):
        return gm.grouped_matmul(a, b, counts, precision=prec)

    def theirs(a, b):
        return jax.lax.ragged_dot(a, b, counts, precision=prec)
    out, vjp = jax.vjp(ours, _poisoned(lhs, used), rhs)
    dlhs, drhs = vjp(_poisoned(dout, used))
    want, want_vjp = jax.vjp(theirs, lhs, rhs)
    want_dlhs, want_drhs = want_vjp(dout)
    for got, ref in ((out, want), (dlhs, want_dlhs), (drhs, want_drhs)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
    _close(out[:used], want[:used], dtype)
    _close(dlhs[:used], want_dlhs[:used], dtype)
    _close(drhs, want_drhs, dtype)
    # the transposed product alone: rhs (G, n, k)
    dt = gm._product(True, prec, _poisoned(dout, used), rhs, counts)
    want_dt = jax.lax.ragged_dot(dout, jnp.swapaxes(rhs, 1, 2), counts,
                                 precision=prec)
    _close(dt[:used], want_dt[:used], dtype)
    # no row of a visited tile past the rows in use is left as VMEM had it
    tail = min(-(-used // gm.ROW_TILE) * gm.ROW_TILE, m)
    assert np.all(np.asarray(out[used:tail]) == 0)


def test_tiles_fit_the_default_vmem_unless_a_width_cannot_be_split():
    # the whole contraction, the columns split at a lane multiple until
    # the blocks fit the compiler's default scoped VMEM (no limit asked)
    tiles = functools.partial(gm._tiles, gm.ROW_TILE, itemsize=2)
    assert tiles(2304, 2048, acc=False) == (2304, 1024, None)
    assert tiles(2688, 1856, acc=True) == (384, 1856, None)
    assert all(tiles(k, n, acc=acc)[2] is None
               for k, n in ((2048, 1536), (768, 2048), (2304, 2048),
                            (1024, 2304), (1856, 2688))
               for acc in (False, True))
    # 1856 columns have no lane-multiple divisor: one block, whose
    # double-buffered weights ask for more than the default
    tk, tn, limit = tiles(2688, 1856, acc=False)
    assert (tk, tn) == (2688, 1856) and limit > 16 << 20


def _layer(activation, seed=3, tokens=80, hidden=32, width=24, experts=32):
    ks = jax.random.split(jax.random.key(seed), 6)
    ways = 2 if activation == "swiglu" else 1
    return (jax.random.normal(ks[0], (tokens, hidden)),
            0.3 * jax.random.normal(ks[1], (hidden, experts)),
            0.3 * jax.random.normal(ks[2], (8, hidden, ways * width)),
            0.3 * jax.random.normal(ks[3], (8, width, hidden)),
            tuple(0.3 * jax.random.normal(k, s) for k, s in zip(
                jax.random.split(ks[4]),
                ((hidden, ways * 40), (40, hidden)))))


@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
def test_expert_layer_never_reads_the_rows_past_those_in_use(
        monkeypatch, activation):
    """The layer through the kernels, whose products write NaN to every
    row past ``sum(counts)``, against the layer through ``ragged_dot``
    (zeros there): the output and all five gradients agree."""
    args = _layer(activation)
    ct = jax.random.normal(jax.random.key(9), args[0].shape)
    kw = dict(top_k=6, expert_offset=8, score="sigmoid", routed_scale=2.5,
              activation=activation)

    def loss(x, router, w_in, down, shared):
        y, _ = moe.dropless_moe(x, router, w_in, down, shared=shared, **kw)
        return jnp.sum(y * ct), y

    def grads():
        (_, y), g = jax.value_and_grad(loss, range(5), has_aux=True)(*args)
        return y, g
    real, calls = gm._product, []

    def poisoning(transpose, precision, lhs, rhs, counts):
        calls.append(transpose)
        return _poisoned(real(transpose, precision, lhs, rhs, counts),
                         jnp.sum(counts))
    monkeypatch.setattr(gm, "_product", poisoning)
    y, got = grads()
    assert sorted(calls) == [False, False, True, True]
    monkeypatch.setattr(moe, "grouped_matmul",
                        lambda a, b, counts, precision:
                        jax.lax.ragged_dot(a, b, counts, precision=precision))
    want_y, want = grads()
    _close(y, want_y, jnp.float32)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(g)))
        _close(g, w, jnp.float32)


def test_gmm_roofline_reads_the_kernels_by_name():
    """The metric's spec and entry, and its reader over a recorded trace
    of two steps: the ``apex_gmm_*`` kernels' time, nothing where only
    the compiler's ``ragged-dot`` kernels ran (the parent's program)."""
    from benchmarks import traceread
    from benchmarks.readers import kernel_roofline
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "gmm_roofline.json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "kernel_roofline",
                    "params": {"prefix": "apex_gmm",
                               "count": "expert_flops"}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "gmm_roofline"]
    assert entry == {
        "name": "gmm_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model (transformer/moe.py)",
        "moves": "step_ms", "workloads": [
            "keye_vl2_30b_a3b_adamw.longctx_s8192",
            "kimi_linear_48b_a3b_adamw.hybrid_s4096",
            "nemotron_twotower_30b_a3b_adamw.ssm_s8192"]}

    def ctx(ops):
        trace = traceread.Trace(devices={0: {
            traceread.MODULE_LINE: [("jit_step(1)", 0.0, 100.0),
                                    ("jit_step(1)", 100.0, 200.0),
                                    ("jit_step(1)", 200.0, 300.0)],
            traceread.OP_LINE: ops}}, host=[])
        return types.SimpleNamespace(
            trace=trace, steady=traceread.steady_window(trace, "step"),
            peaks={"flops_per_s": 1e9}, counts={"expert_flops": 3.3})
    ops = [("%apex_gmm_fwd.3 = bf16[64,32]{1,0} custom-call(...)", 10, 20),
           ("%apex_gmm_dlhs = bf16[64,32]{1,0} custom-call(...)", 120, 125),
           ("%apex_gmm_dw.1 = bf16[4,32,32]{2,1,0} custom-call(...)",
            130, 137),
           ("%ragged-dot-none.3 = bf16[64,32]{1,0} custom-call(...)", 40, 60),
           ("%fusion.7 = f32[8]{0} fusion(...)", 60, 90)]
    # 3.3 FLOPs at 1e9 FLOP/s are 3.3 ns a step; the kernels ran 22 ns
    # over the two steps, 11 a step
    assert kernel_roofline.read(ctx(ops), **spec["params"]) \
        == pytest.approx(30.0)
    assert kernel_roofline.read(ctx(ops[3:]), **spec["params"]) is None
