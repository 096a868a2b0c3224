"""Bucketed flat-parameter optimizer path (ISSUE 2 tentpole).

Contracts:
  * every fused optimizer steps through the bucketed flat kernels BY
    DEFAULT and matches the per-leaf oracle path (f32 and bf16+masters,
    per-dtype tolerances);
  * params/masters/opt_state stay packed between steps — the per-leaf
    view is a lazy property;
  * state_dict layout is unchanged: old per-leaf checkpoints load into
    bucketed optimizers and vice versa;
  * ``fuse_buckets=False`` is a clean escape hatch;
  * amp's found_inf flag skips the update branch-free.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.multi_tensor_apply import BucketPlan
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)

OPTS = [
    (FusedAdam, dict(lr=1e-2, weight_decay=0.01)),
    (FusedSGD, dict(lr=0.1, momentum=0.9, weight_decay=1e-4)),
    (FusedAdagrad, dict(lr=1e-2, weight_decay=0.01)),
    (FusedNovoGrad, dict(lr=1e-2, weight_decay=0.01)),
    (FusedLAMB, dict(lr=1e-2, weight_decay=0.01)),
]


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-6)


def _params(dtype, key=0):
    """Several layers of mixed big/small leaves (a realistic pytree the
    packer folds into one bucket per dtype)."""
    ks = jax.random.split(jax.random.key(key), 3)
    return {
        "layer1": {"w": jax.random.normal(
            ks[0], (16, 8), jnp.float32).astype(dtype),
            "b": jnp.zeros((8,), dtype)},
        "layer2": {"w": jax.random.normal(
            ks[1], (8, 4), jnp.float32).astype(dtype),
            "scale": jnp.ones((4,), dtype)},
        "head": jax.random.normal(ks[2], (4, 3), jnp.float32).astype(dtype),
    }


def _grads(params, seed):
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.key(seed), p.shape,
                                    jnp.float32).astype(p.dtype) * 0.1,
        params)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cls,kw", OPTS,
                         ids=[c.__name__ for c, _ in OPTS])
def test_bucketed_matches_per_leaf(cls, kw, dtype):
    params = _params(dtype)
    ref = cls(params, fuse_buckets=False, **kw)
    buck = cls(params, fuse_buckets=True, **kw)
    assert buck.fuse_buckets and not ref.fuse_buckets
    for s in range(3):
        g = _grads(params, 100 + s)
        ref.step(g)
        buck.step(g)
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(buck.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol(dtype))
    if dtype == jnp.bfloat16:       # masters stepped, both packed+not
        for a, b in zip(jax.tree_util.tree_leaves(ref.masters),
                        jax.tree_util.tree_leaves(buck.masters)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def test_default_is_bucketed_with_escape_hatch():
    p = _params(jnp.float32)
    assert FusedAdam(p, lr=1e-3).fuse_buckets
    assert not FusedAdam(p, lr=1e-3, fuse_buckets=False).fuse_buckets


def test_params_stay_packed_between_steps():
    p = _params(jnp.float32)
    opt = FusedAdam(p, lr=1e-2)
    g = _grads(p, 7)
    opt.step(g)
    # canonical representation is the per-bucket flat buffers
    assert isinstance(opt._param_bufs, list)
    assert sum(b.size for b in opt._param_bufs) \
        == sum(l.size for l in jax.tree_util.tree_leaves(p))
    # the property unpacks lazily and caches until the next step
    v1 = opt.params
    assert opt.params is v1
    opt.step(g)
    assert opt.params is not v1


@pytest.mark.parametrize("cls,kw", OPTS,
                         ids=[c.__name__ for c, _ in OPTS])
def test_state_dict_roundtrip_across_packing(cls, kw):
    """Per-leaf checkpoints load into bucketed optimizers (and back):
    the serialized layout is the per-leaf torch shape either way."""
    params = _params(jnp.float32)
    g = _grads(params, 3)

    old = cls(params, fuse_buckets=False, **kw)
    old.step(g)
    sd = old.state_dict()
    new = cls(old.params, fuse_buckets=True, **kw)
    new.load_state_dict(sd)
    old.step(g)
    new.step(g)
    for a, b in zip(jax.tree_util.tree_leaves(old.params),
                    jax.tree_util.tree_leaves(new.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # bucketed state_dict serializes the SAME per-leaf layout
    sd2 = new.state_dict()
    assert (jax.tree_util.tree_structure(sd2["state"])
            == jax.tree_util.tree_structure(sd["state"]))
    back = cls(new.params, fuse_buckets=False, **kw)
    back.load_state_dict(sd2)
    back.step(g)
    old.step(g)
    for a, b in zip(jax.tree_util.tree_leaves(old.params),
                    jax.tree_util.tree_leaves(back.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_novograd_scalar_state_layout_preserved():
    """NovoGrad's per-tensor second moment serializes as per-leaf
    SCALARS (the pre-bucketing layout) even though it lives packed as
    one vector per bucket."""
    params = _params(jnp.float32)
    opt = FusedNovoGrad(params, lr=1e-2)
    opt.step(_grads(params, 1))
    sd = opt.state_dict()
    for leaf in jax.tree_util.tree_leaves(sd["state"]["exp_avg_sq"]):
        assert np.asarray(leaf).shape == ()
    for leaf, p in zip(
            jax.tree_util.tree_leaves(sd["state"]["exp_avg"]),
            jax.tree_util.tree_leaves(params)):
        assert np.asarray(leaf).shape == p.shape


def test_found_inf_skips_update_and_step_clock():
    params = _params(jnp.float32)
    g = _grads(params, 5)
    opt = FusedAdam(params, lr=1e-2)
    p0 = np.asarray(opt.params["head"])
    opt.step(g, found_inf=jnp.int32(1))
    np.testing.assert_array_equal(p0, np.asarray(opt.params["head"]))
    assert int(opt.step_count) == 0
    opt.step(g, found_inf=jnp.int32(0))
    assert int(opt.step_count) == 1
    assert not np.allclose(p0, np.asarray(opt.params["head"]))
    # matches an unconditional step (the skipped call left no trace)
    ref = FusedAdam(params, lr=1e-2)
    ref.step(g)
    np.testing.assert_allclose(np.asarray(ref.params["head"]),
                               np.asarray(opt.params["head"]),
                               rtol=1e-6, atol=1e-7)


def test_found_inf_from_flat_scale():
    """amp interop: flat_scale's on-device overflow flag drives the
    branch-free skip end to end."""
    from apex_tpu.multi_tensor_apply import flatten
    from apex_tpu.ops.multi_tensor import flat_scale

    params = _params(jnp.float32)
    g = _grads(params, 5)
    bad = {**g, "head": g["head"].at[0, 0].set(jnp.inf)}
    opt = FusedAdam(params, lr=1e-2)
    p0 = np.asarray(opt.params["head"])
    for grads in (bad, g):
        flat = flatten([jnp.ravel(l) for l in
                        jax.tree_util.tree_leaves(grads)])
        _, flag = flat_scale(flat, 1.0)
        opt.step(grads, found_inf=flag)
    assert int(opt.step_count) == 1      # only the finite step counted
    assert not np.allclose(p0, np.asarray(opt.params["head"]))


def test_bucketed_offload_state_matches_resident():
    params = _params(jnp.float32)
    g = _grads(params, 9)
    ref = FusedAdam(params, lr=1e-2, weight_decay=0.01)
    off = FusedAdam(params, lr=1e-2, weight_decay=0.01,
                    offload_state=True)
    # bucketed state offloads as WHOLE flat buffers
    for leaf in jax.tree_util.tree_leaves(off.opt_state):
        assert leaf.ndim == 1
        assert leaf.sharding.memory_kind in ("pinned_host",
                                             "unpinned_host")
    for _ in range(2):
        ref.step(g)
        off.step(g)
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(off.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_mixed_dtype_tree_packs_per_dtype_buckets():
    """A tree with f32 AND bf16 leaves packs into one bucket per dtype
    and still matches the per-leaf path."""
    params = {"big": jax.random.normal(jax.random.key(0), (32, 8)),
              "half": jax.random.normal(jax.random.key(1),
                                        (16,)).astype(jnp.bfloat16)}
    # mixed tree => low-precision => masters by default; keep this test
    # about dtype bucketing, not masters
    ref = FusedSGD(params, lr=0.1, momentum=0.9, master_weights=False,
                   fuse_buckets=False)
    buck = FusedSGD(params, lr=0.1, momentum=0.9, master_weights=False,
                    fuse_buckets=True)
    assert len(buck._plan.buckets) == 2
    g = _grads(params, 11)
    for _ in range(2):
        ref.step(g)
        buck.step(g)
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(buck.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestBucketPlan:
    def test_declines_non_float_and_empty(self):
        assert BucketPlan.from_tree({}) is None
        assert BucketPlan.from_tree(
            {"w": jnp.ones((4,)), "i": jnp.zeros((2,), jnp.int32)}) is None

    def test_optimizer_falls_back_when_unpackable(self):
        params = {"w": jnp.ones((8,)), "steps": jnp.zeros((1,), jnp.int32)}
        opt = FusedSGD(params, lr=0.1)
        assert not opt.fuse_buckets      # graceful per-leaf fallback

    def test_roundtrip_and_offsets(self):
        tree = {"a": jnp.arange(6.0).reshape(2, 3),
                "b": jnp.arange(4.0) + 10}
        plan = BucketPlan.from_tree(tree)
        bufs = plan.pack(tree)
        assert len(bufs) == 1 and bufs[0].shape == (10,)
        back = plan.unpack(bufs)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_segment_sizes_follow_the_leaves(self):
        tree = {"a": jnp.ones((3, 2)), "b": jnp.ones((5,))}
        plan = BucketPlan.from_tree(tree)
        sizes = plan.segment_sizes(0)
        assert sizes == (6, 5) and all(type(s) is int for s in sizes)
        assert sum(sizes) == plan.buckets[0].size == 11
        # contiguous and in order: leaf j starts where sizes[:j] end
        assert [s.offset for s in plan.buckets[0].leaves] == [0, 6]
        assert len(sizes) == len(plan.buckets[0].leaves) == 2


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["kernels", "xla_oracle"])
@pytest.mark.parametrize("cls", [FusedLAMB, FusedNovoGrad],
                         ids=lambda c: c.__name__)
def test_segmented_step_program_has_no_scatter_or_gather(cls, pallas,
                                                         monkeypatch):
    """Per-tensor norms come from the plan's static segment sizes: the
    jitted bucketed step scatters nothing through an element->segment
    id vector, gathers nothing from one, and holds no integer array of
    bucket size.  Both ``op_enabled("multi_tensor")`` branches."""
    if not pallas:
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    params = _params(jnp.bfloat16)
    opt = cls(params, lr=1e-2, weight_decay=0.01, master_weights=True)
    assert opt.fuse_buckets and len(opt._plan.buckets) == 1
    n = opt._plan.buckets[0].size
    assert len(opt._plan.segment_sizes(0)) == 5
    text = opt._jit_step.lower(
        *opt._step_args(_grads(params, 7), 1.0, jnp.int32(0))).as_text()
    assert "stablehlo.reduce" in text           # the norms are there
    for word in ("scatter", "gather", f"tensor<{n}xi32>",
                 f"tensor<{n}xui32>", f"tensor<{n}xi64>"):
        assert word not in text, word


def test_functional_step_layout_detection():
    """functional_step must route by the STATE's actual layout: a
    per-leaf state whose top-level pytree is a list of the right length
    (list-shaped params) is NOT the packed layout (code-review catch)."""
    params = [jnp.ones((4, 4), jnp.float32),
              jnp.ones((3, 3), jnp.bfloat16)]
    g = [jnp.full((4, 4), 0.1), jnp.full((3, 3), 0.1, jnp.bfloat16)]
    opt = FusedAdam(params, lr=1e-2, master_weights=False)
    perleaf_state = opt.init_state(params)
    assert not opt._state_is_packed(perleaf_state)
    assert opt._state_is_packed(opt.opt_state)
    newp, _ = opt.functional_step(params, perleaf_state, g, jnp.int32(1))
    newp2, _ = opt.functional_step(params, opt.opt_state, g, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(newp[0]), np.asarray(newp2[0]),
                               rtol=1e-6, atol=1e-7)


def test_bucketing_microbench_smoke():
    """The per-leaf-vs-bucketed microbench harness runs end to end on
    tiny shapes (CPU: proves the harness, not performance)."""
    from apex_tpu.optimizers.bucketing_bench import \
        bench_optimizer_bucketing
    r = bench_optimizer_bucketing(layers=3, hidden=32, iters=2, reps=1)
    assert r["optim_step_perleaf_ms"] > 0
    assert r["optim_step_bucketed_ms"] > 0
    assert r["optim_bucketing_speedup"] > 0
    assert r["optim_leaves"] == 12
