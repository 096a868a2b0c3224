"""Flat end-to-end AMP gradient pipeline (amp/flat_pipeline.py).

Equivalence against the per-leaf amp oracle (unscale_grads +
check_finite + clip_grad_norm + per-leaf optimizer step), overflow
handling, clip-coefficient parity, packed-grads step() parity for all
five fused optimizers, bucket-granular all-reduce, and the structural
op-count guarantee: ONE gradient pack per bucket, ZERO per-leaf
unscale/clip ops in the hot step's jaxpr.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, comm
from apex_tpu.contrib.clip_grad import clip_grad_norm
from apex_tpu.multi_tensor_apply.packer import BucketPlan
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)

tree_leaves = jax.tree_util.tree_leaves
tree_map = jax.tree_util.tree_map


def _params(dtype=jnp.float32, layers=3, hidden=24):
    keys = jax.random.split(jax.random.key(0), layers)
    return {
        f"l{i}": {
            "w": (jax.random.normal(keys[i], (hidden, hidden)) * 0.3
                  ).astype(dtype),
            "b": jnp.zeros((hidden,), dtype),
            "s": jnp.ones((hidden,), dtype),
        }
        for i in range(layers)
    }


def _grads_like(params, scale=1.0, seed=7):
    keys = jax.random.split(jax.random.key(seed),
                            len(tree_leaves(params)))
    flat, treedef = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(treedef, [
        (jax.random.normal(k, l.shape) * scale).astype(l.dtype)
        for k, l in zip(keys, flat)])


def _assert_trees_close(a, b, **kw):
    for x, y in zip(tree_leaves(a), tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **kw)


# ---------------------------------------------------------------------------
# fused kernel vs per-leaf amp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flat_unscale_norm_matches_perleaf_amp(dtype):
    """pack + flat_unscale_norm == check_finite + unscale_grads +
    global norm, for f32 and bf16 gradient trees."""
    params = _params(dtype)
    grads = _grads_like(params, scale=512.0)   # "loss-scaled" magnitudes
    state = amp.LossScaleState.create(2.0 ** 9)

    # per-leaf oracle
    fi_ref = amp.check_finite(grads)
    g_ref = amp.unscale_grads(grads, state)
    norm_ref = jnp.sqrt(sum(
        jnp.sum(l.astype(jnp.float32) ** 2) for l in tree_leaves(g_ref)))

    plan = BucketPlan.from_tree(grads)
    pipe = amp.FlatGradPipeline(plan=plan)
    flat = pipe.unscale_and_norm(pipe.pack(grads), state)

    assert int(flat.found_inf) == int(fi_ref) == 0
    # kernel norm accumulates pre-rounding f32; per-leaf norm reads the
    # rounded unscaled tree — bf16 tolerance covers the rounding delta
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(flat.grad_norm), float(norm_ref),
                               rtol=tol)
    _assert_trees_close(pipe.grads_tree(flat), g_ref,
                        rtol=tol, atol=1e-6)
    # kernel vs its own _ref oracle, exact same contract
    for buf in pipe.pack(grads):
        o_k, n_k, f_k = mt.flat_unscale_norm(buf, 1.0 / state.loss_scale)
        o_r, n_r, f_r = mt.flat_unscale_norm_ref(buf,
                                                 1.0 / state.loss_scale)
        np.testing.assert_allclose(np.asarray(o_k, np.float32),
                                   np.asarray(o_r, np.float32), rtol=1e-6)
        np.testing.assert_allclose(float(n_k), float(n_r), rtol=1e-5)
        assert int(f_k) == int(f_r)


@pytest.mark.parametrize("bad", [jnp.inf, -jnp.inf, jnp.nan])
def test_nonfinite_injection_drives_found_inf_and_skip(bad):
    params = _params()
    grads = _grads_like(params)
    grads["l1"]["w"] = grads["l1"]["w"].at[2, 3].set(bad)
    state = amp.LossScaleState.create(2.0 ** 4)

    opt = FusedAdam(params, lr=1e-2)
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0)
    flat = pipe.unscale_and_norm(pipe.pack(grads), state)
    assert int(flat.found_inf) == 1
    # NaN-safe clip coefficient: stays 1.0, never NaN
    assert float(flat.clip_coef) == 1.0

    before = opt.params
    new_params = pipe.step(flat)        # branch-free skip
    _assert_trees_close(new_params, before, rtol=0, atol=0)
    assert int(opt.step_count) == 0     # skipped step keeps the clock

    # clean grads on the same optimizer DO step
    flat2 = pipe.unscale_and_norm(pipe.pack(_grads_like(params)), state)
    assert int(flat2.found_inf) == 0
    stepped = pipe.step(flat2)
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(tree_leaves(stepped), tree_leaves(before)))
    assert int(opt.step_count) == 1


def test_clip_coef_matches_clip_grad_norm():
    params = _params()
    grads = _grads_like(params, scale=3.0)   # norm safely above max_norm
    state = amp.LossScaleState.create(1.0)   # isolate the clip math

    max_norm = 1.5
    pipe = amp.FlatGradPipeline(params=params, max_grad_norm=max_norm)
    flat = pipe.unscale_and_norm(pipe.pack(grads), state)

    clipped_ref, norm_ref = clip_grad_norm(grads, max_norm)
    np.testing.assert_allclose(float(flat.grad_norm), float(norm_ref),
                               rtol=1e-6)
    # same formula: max_norm / (norm + eps)
    np.testing.assert_allclose(
        float(flat.clip_coef),
        float(jnp.minimum(max_norm / (norm_ref + 1e-6), 1.0)), rtol=1e-6)
    # applying clip_coef to the flat buffers == the clipped tree
    _assert_trees_close(
        pipe.grads_tree(flat._replace(
            bufs=[b * flat.clip_coef for b in flat.bufs])),
        clipped_ref, rtol=1e-5, atol=1e-7)
    # below max_norm: no clipping
    pipe2 = amp.FlatGradPipeline(params=params, max_grad_norm=1e6)
    assert float(pipe2.unscale_and_norm(
        pipe2.pack(grads), state).clip_coef) == 1.0


def test_clip_grad_norm_packed_delegation():
    grads = _params()   # any float tree works as "grads"
    plan = BucketPlan.from_tree(grads)
    bufs = plan.pack_grads(grads)
    c_tree, n_tree = clip_grad_norm(grads, 0.7)
    c_bufs, n_bufs = clip_grad_norm(bufs, 0.7)
    assert isinstance(c_bufs, list) and len(c_bufs) == len(bufs)
    np.testing.assert_allclose(float(n_tree), float(n_bufs), rtol=1e-6)
    _assert_trees_close(plan.unpack_grads(c_bufs), c_tree, rtol=1e-6)


# ---------------------------------------------------------------------------
# packed-grads step() parity, all five optimizers
# ---------------------------------------------------------------------------

_OPTIMIZERS = [
    (FusedAdam, dict(lr=1e-2)),
    (FusedSGD, dict(lr=1e-2, momentum=0.9)),
    (FusedAdagrad, dict(lr=1e-2)),
    (FusedNovoGrad, dict(lr=1e-2)),
    (FusedLAMB, dict(lr=1e-2, max_grad_norm=0.0)),
]


@pytest.mark.parametrize("cls,kw", _OPTIMIZERS,
                         ids=[c.__name__ for c, _ in _OPTIMIZERS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_step_matches_unpacked(cls, kw, dtype):
    """step(packed buffers) == step(pytree), f32 and bf16+masters,
    including a traced clip_coef folded into the kernels."""
    params = _params(dtype)
    opt_tree = cls(params, **kw)
    opt_pack = cls(params, **kw)
    assert opt_pack.fuse_buckets
    clip = jnp.float32(0.75)
    for s in range(2):   # two steps: momentum/first_run paths both run
        grads = _grads_like(params, seed=10 + s)
        p_tree = opt_tree.step(grads, clip_coef=clip)
        bufs = opt_pack._plan.pack_grads(grads)
        p_pack = opt_pack.step(bufs, clip_coef=clip)
        _assert_trees_close(p_tree, p_pack, rtol=1e-6, atol=1e-7)
        if opt_tree.masters is not None:
            _assert_trees_close(opt_tree.masters, opt_pack.masters,
                                rtol=1e-6, atol=1e-7)


def test_step_accepts_flat_grads_bundle():
    """step(FlatGrads) pulls bufs + found_inf + clip_coef from the
    bundle; equivalent to passing them explicitly."""
    params = _params()
    grads = _grads_like(params)
    state = amp.LossScaleState.create(2.0 ** 3)
    opt_a = FusedAdam(params, lr=1e-2)
    opt_b = FusedAdam(params, lr=1e-2)
    pipe = amp.FlatGradPipeline(optimizer=opt_a, max_grad_norm=0.5)
    flat = pipe.unscale_and_norm(pipe.pack(grads), state)
    p_a = opt_a.step(flat)
    p_b = opt_b.step(flat.bufs, found_inf=flat.found_inf,
                     clip_coef=flat.clip_coef)
    _assert_trees_close(p_a, p_b, rtol=0, atol=0)


def test_clip_coef_fold_equals_prescaled_grads():
    """clip_coef folding == multiplying the gradients by clip_coef."""
    params = _params()
    grads = _grads_like(params)
    for cls, kw in _OPTIMIZERS:
        o1, o2 = cls(params, **kw), cls(params, **kw)
        p1 = o1.step(tree_map(lambda g: g * 0.5, grads))
        p2 = o2.step(grads, clip_coef=jnp.float32(0.5))
        _assert_trees_close(p1, p2, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# full AMP train step: flat pipeline vs per-leaf oracle
# ---------------------------------------------------------------------------

def _loss_fn(p, x):
    h = x
    for name in sorted(p):
        h = jnp.tanh(h @ p[name]["w"].astype(jnp.float32)
                     + p[name]["b"].astype(jnp.float32))
        h = h * p[name]["s"].astype(jnp.float32)
    return jnp.sum(h ** 2) * 0.1


@pytest.mark.parametrize("opt_level", ["O1", "O2"])
def test_full_amp_step_flat_matches_perleaf(opt_level):
    """scaled_value_and_grad -> pack -> fused unscale/norm -> packed
    clipped step == the per-leaf chain, for pure-f32 (O1) and
    bf16+masters (O2)."""
    params0 = _params(jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 24))
    params, amp_state = amp.initialize(params0, opt_level=opt_level)
    state = amp_state.scaler
    masters = amp_state.master_params
    max_norm = 0.5

    opt_ref = FusedAdam(params, lr=1e-2, masters=masters,
                        fuse_buckets=False)
    opt_flat = FusedAdam(params, lr=1e-2, masters=masters,
                         fuse_buckets=True)
    assert opt_flat.fuse_buckets

    # per-leaf oracle chain
    loss_ref, grads, fi = amp.scaled_value_and_grad(
        _loss_fn, state, params, x)
    clipped, _ = clip_grad_norm(grads, max_norm)
    p_ref = opt_ref.step(clipped, found_inf=fi)

    # flat pipeline chain
    pipe = amp_state.flat_pipeline(optimizer=opt_flat,
                                   max_grad_norm=max_norm)
    loss_flat, flat = pipe.scaled_value_and_grad(_loss_fn, state,
                                                 params, x)
    p_flat = pipe.step(flat)

    np.testing.assert_allclose(float(loss_ref), float(loss_flat),
                               rtol=1e-6)
    tol = dict(rtol=1e-5, atol=1e-6) if opt_level == "O1" \
        else dict(rtol=2e-2, atol=2e-4)   # bf16 params; norm rounding
    _assert_trees_close(p_ref, p_flat, **tol)
    if opt_ref.masters is not None:
        # f32 masters carry the true update; tighter than the bf16 params
        _assert_trees_close(opt_ref.masters, opt_flat.masters,
                            rtol=5e-4, atol=1e-6)


def test_scaler_entry_grads_layout_flat():
    """amp.scaled_value_and_grad(grads_layout='flat') returns a
    FlatGrads bundle equal to the tree layout's grads."""
    params = _params()
    x = jax.random.normal(jax.random.key(2), (4, 24))
    state = amp.LossScaleState.create(2.0 ** 8)
    loss_t, grads, fi_t = amp.scaled_value_and_grad(
        _loss_fn, state, params, x)
    # plan=None: a cached plan is derived from the gradient tree
    loss_f, flat, fi_f = amp.scaled_value_and_grad(
        _loss_fn, state, params, x, grads_layout="flat")
    assert isinstance(flat, amp.FlatGrads)
    assert int(fi_t) == int(fi_f) == 0
    np.testing.assert_allclose(float(loss_t), float(loss_f), rtol=1e-6)
    plan = BucketPlan.from_tree(grads)
    _assert_trees_close(plan.unpack_grads(flat.bufs), grads,
                        rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError):
        amp.scaled_value_and_grad(_loss_fn, state, params, x,
                                  grads_layout="banana")


# ---------------------------------------------------------------------------
# bucket-granular data-parallel all-reduce
# ---------------------------------------------------------------------------

def test_bucketed_allreduce_matches_perleaf():
    from apex_tpu.parallel import (Reducer, all_reduce_gradients)
    mesh = comm.initialize(data=8)
    params = _params()
    plan = BucketPlan.from_tree(params)
    gx = jax.random.normal(jax.random.key(3),
                           (8,) + (24, 24))   # per-shard w grads

    def per_leaf(gs):
        tree = _grads_like(params)
        tree["l0"]["w"] = gs[0]
        return all_reduce_gradients(tree, comm.AXIS_DATA)

    def bucketed(gs):
        tree = _grads_like(params)
        tree["l0"]["w"] = gs[0]
        return Reducer(axis_name=comm.AXIS_DATA, plan=plan).reduce(tree)

    def bucketed_packed(gs):
        tree = _grads_like(params)
        tree["l0"]["w"] = gs[0]
        bufs = Reducer(axis_name=comm.AXIS_DATA, plan=plan).reduce(
            plan.pack_grads(tree))
        return plan.unpack_grads(bufs)

    sm = lambda f: jax.jit(comm.shard_map(
        f, mesh, in_specs=P(comm.AXIS_DATA), out_specs=P()))
    r_leaf = sm(per_leaf)(gx)
    r_bucket = sm(bucketed)(gx)
    r_packed = sm(bucketed_packed)(gx)
    _assert_trees_close(r_leaf, r_bucket, rtol=1e-6, atol=1e-7)
    _assert_trees_close(r_leaf, r_packed, rtol=1e-6, atol=1e-7)
    comm.destroy()


# ---------------------------------------------------------------------------
# structural guarantee: ONE pack, zero per-leaf amp ops — now owned by
# the shared apexverify spec (apex_tpu/lint/semantic), which this test
# drives; the per-leaf contrast (not a library invariant) stays local
# but uses the same shared walker, so neither side can silently weaken.
# ---------------------------------------------------------------------------

def test_op_count_one_pack_zero_perleaf_amp_ops():
    """The jitted flat AMP train step contains exactly ONE gradient
    pack per bucket, 1 pallas_call per bucket and ZERO per-leaf
    unscale/clip/finite-check ops — asserted by the registered
    `amp.flat_pipeline_step` invariant spec; the per-leaf oracle step
    contains one finite check per leaf (local contrast)."""
    from apex_tpu.lint import semantic
    from apex_tpu.ops._dispatch import op_enabled

    res = semantic.verify_spec(semantic.get_spec("amp.flat_pipeline_step"))
    assert res.ok, res.failures
    # the spec really checked the invariants this test used to own
    checked = set(res.checked)
    assert {"bucket_concats", "no_host_transfer",
            "is_finite_max", "no_f64"} <= checked, checked
    if op_enabled("multi_tensor"):
        # exactly 1 pallas_call per bucket (unscale_norm; the Adam
        # update is XLA sweeps): clip folds into its grad scaling
        assert "pallas_calls" in checked, checked

    # contrast: the per-leaf oracle walks every leaf
    params = _params()
    x = jax.random.normal(jax.random.key(4), (4, 24))
    state = amp.LossScaleState.create()
    n_leaves = len(tree_leaves(params))
    opt_pl = FusedAdam(params, lr=1e-3, fuse_buckets=False)

    def per_leaf_step(ptree, opt_state, scaler, x, step):
        loss, grads, fi = amp.scaled_value_and_grad(_loss_fn, scaler,
                                                    ptree, x)
        clipped, _ = clip_grad_norm(grads, 1.0)
        new_p, new_state = opt_pl.functional_step(
            ptree, opt_state, clipped, step)
        return loss, new_p, new_state

    jaxpr_pl = jax.make_jaxpr(per_leaf_step)(
        params, opt_pl.opt_state, state, x, jnp.int32(1))
    counts_pl = semantic.jaxprs.primitive_counts(jaxpr_pl)
    assert counts_pl.get("is_finite", 0) >= n_leaves

    # the bucketed step's finite checks stay strictly below per-leaf:
    # the spec pinned them at <= n_buckets (0 with kernels enabled),
    # and every tiny spec tree has more leaves than buckets
    opt_b = FusedAdam(params, lr=1e-3)
    assert len(opt_b._plan.buckets) < n_leaves


# ---------------------------------------------------------------------------
# bench harness smoke (tier-1 keeps the tooling runnable, like
# bucketing_bench)
# ---------------------------------------------------------------------------

def test_amp_pipeline_microbench_smoke():
    from apex_tpu.optimizers.bucketing_bench import bench_amp_pipeline
    r = bench_amp_pipeline(layers=3, hidden=32, iters=2, reps=1)
    assert r["amp_step_per_leaf_ms"] > 0
    assert r["amp_step_flat_ms"] > 0
    assert r["amp_pipeline_speedup"] > 0
    assert r["amp_leaves"] == 12
