"""A bucket that holds one leaf keeps that leaf's shape.

Contracts:
  * every buffer of a one-leaf bucket (packed parameters, masters,
    each state field that mirrors the parameters, the gradient the
    step reads) has the leaf's shape; its pack is the leaf and its
    unpack the buffer, beside multi-leaf buckets that stay 1-D;
  * the update is the same math: FusedAdam's step equals, to the bit,
    ``flat_adam`` over the raveled buffers, and every fused optimizer
    matches its per-leaf path;
  * with masters, the one-leaf bucket's model-dtype buffer is the leaf
    ``params`` hands out, and no step deletes it; a caller that donates
    it is told so by what reads it until the next step;
  * a plain list of leaves, each alone in a bucket, has the structure
    of its packed buffers: a per-leaf state or gradient list is read
    per leaf, the optimizer's ``PackedState`` and a ``FlatGrads``
    bundle in bucket order;
  * what else reads the packed layout keeps working: a snapshot held
    flat loads (the v2 checkpoint's payload is flat), ``rechunk``, the
    fp8 slots, the DDP bucket reduce, the flat AMP pipeline, whose
    kernels tile a leaf-shaped buffer in its own shape, and packed
    ``clip_grad_norm``;
  * the optimizer reports its leaf-shaped buckets once, at
    construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, checkpoint, comm
from apex_tpu.amp import fp8
from apex_tpu.amp.flat_pipeline import FlatGradPipeline
from apex_tpu.contrib.clip_grad import clip_grad_norm
from apex_tpu.multi_tensor_apply import BucketPlan
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)
from apex_tpu.parallel.distributed import all_reduce_flat_buffers
from apex_tpu.telemetry import hostmetrics

OPTS = {
    "adam": (FusedAdam, dict(lr=1e-2, weight_decay=0.01)),
    "lamb": (FusedLAMB, dict(lr=1e-2, weight_decay=0.01)),
    "sgd": (FusedSGD, dict(lr=0.1, momentum=0.9, weight_decay=1e-4)),
    "novograd": (FusedNovoGrad, dict(lr=1e-2, weight_decay=0.01)),
    "adagrad": (FusedAdagrad, dict(lr=1e-2, weight_decay=0.01)),
}
DTYPES = {"bf16_masters": jnp.bfloat16, "f32": jnp.float32}
# 384 float32 a bucket: "a_emb" and "d_head" fill one each, "e_stack"
# is left alone before the end; "b_w" and "c_b" share the flat one
CAP = 384 * 4
SHAPES = [(24, 16), (54,), (16, 24), (2, 3, 5)]


def _params(dtype):
    ks = jax.random.split(jax.random.key(0), 4)

    def make(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return {"a_emb": make(ks[0], (24, 16)), "b_w": make(ks[1], (8, 6)),
            "c_b": jnp.ones((6,), dtype), "d_head": make(ks[2], (16, 24)),
            "e_stack": make(ks[3], (2, 3, 5))}


def _grads(params, seed):
    return jax.tree_util.tree_map(
        lambda p: (jax.random.normal(jax.random.key(seed), p.shape,
                                     jnp.float32) * 0.1).astype(p.dtype),
        params)


def _build(name, dtype, **kw):
    cls, hypers = OPTS[name]
    opt = cls(_params(dtype), max_bucket_bytes=CAP, **hypers, **kw)
    assert [b.shape for b in opt._plan.buckets] == SHAPES
    return opt


def _bits(tree):
    return [np.atleast_1d(np.asarray(x)).view(np.uint8).copy()
            for x in jax.tree_util.tree_leaves(tree)]


def _same_bits(got, want):
    got, want = _bits(got), _bits(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _close(got, want, dtype):
    tol = (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
           else dict(rtol=2e-5, atol=2e-6))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


# ---- the plan --------------------------------------------------------------

@pytest.mark.parametrize("field", ["params", "mirror_state", "scalar_state",
                                   "row_state"])
def test_round_trips_in_leaf_shape(field):
    params = _params(jnp.float32)
    plan = BucketPlan.from_tree(params, max_bucket_bytes=CAP)
    assert [d["leaf_shaped"] for d in plan.describe()] \
        == [True, False, True, True]
    if field == "params":
        bufs = plan.pack(params)
        assert [b.shape for b in bufs] == SHAPES
        # the pack of a one-leaf bucket is the leaf, its unpack the buffer
        assert bufs[0] is params["a_emb"] and bufs[2] is params["d_head"]
        back = plan.unpack(bufs)
        assert back["a_emb"] is bufs[0]
        _same_bits(back, params)
        assert plan.is_packed(bufs)
        assert not plan.is_packed(jax.tree_util.tree_leaves(params))
        assert not plan.is_packed([jnp.ravel(b) for b in bufs])
        return
    if field == "mirror_state":
        tree = jax.tree_util.tree_map(lambda x: x * 3.0, params)
        want = SHAPES
    elif field == "scalar_state":
        tree = jax.tree_util.tree_map(lambda x: jnp.sum(x), params)
        want = [(1,), (2,), (1,), (1,)]
    else:                           # the fp8 amax history: a row a leaf
        tree = jax.tree_util.tree_map(
            lambda x: jnp.arange(4.0) + jnp.sum(x), params)
        want = [(1, 4), (2, 4), (1, 4), (1, 4)]
    bufs = plan.pack_state_field(tree)
    assert [b.shape for b in bufs] == want
    _same_bits(plan.unpack_state_field(bufs), tree)


@pytest.mark.parametrize("shape", [(256, 1024), (8, 32, 256), (24, 16)],
                         ids=["own_shape", "stacked", "raveled"])
def test_flat_kernels_on_a_leaf_shaped_buffer(shape):
    """The flat Pallas kernels take a buffer in its leaf's shape, tiled
    in that shape where its last axis is whole lanes (the first two
    ids) and raveled where not, and give their oracles' results in
    that shape."""
    tiles = mt._as_tiles(jnp.zeros(shape), jnp.zeros(shape, jnp.bfloat16))
    assert (tiles[0].x2d.shape[-1] == shape[-1]) == (shape[-1] % 128 == 0)
    assert tiles[0].grid == {3: (2, 1), 2: (2, 2)}.get(
        len(shape) if shape[-1] % 128 == 0 else 0, (1, 1))
    k = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(k[0], shape, jnp.float32)
    g = jax.random.normal(k[1], shape, jnp.float32).astype(jnp.bfloat16)
    pairs = [
        (mt.flat_scale(x, 0.5), mt.flat_scale_ref(x, 0.5)),
        (mt.flat_axpby(2.0, x, -1.0, g), mt.flat_axpby_ref(2.0, x, -1.0, g)),
        (mt.flat_accumulate(x, g, 0.25), mt.flat_accumulate_ref(x, g, 0.25)),
        (mt.flat_unscale_norm(g, 0.5), mt.flat_unscale_norm_ref(g, 0.5)),
        ((mt.flat_l2norm(x),), (mt.flat_l2norm_ref(x),)),
    ]
    for got, want in pairs:
        assert got[0].shape == want[0].shape
        _close(got, want, jnp.float32)


# ---- the same math ---------------------------------------------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_adam_step_is_flat_adam_on_raveled_buffers(dtype):
    """One FusedAdam step over a plan with one-leaf buckets equals, to
    the bit, ``flat_adam`` over the flat buffers as they were packed
    before buckets kept their leaf's shape."""
    opt = _build("adam", DTYPES[dtype])
    work0 = opt._master_bufs or opt._param_bufs
    state0 = opt.opt_state
    old = [jnp.ravel(b) for b in work0]
    old_m = [jnp.ravel(b) for b in state0["exp_avg"]]
    old_v = [jnp.ravel(b) for b in state0["exp_avg_sq"]]
    grads = _grads(opt.params, 1)
    flat_g = [jnp.concatenate([jnp.ravel(grads[k]) for k in keys])
              for keys in (["a_emb"], ["b_w", "c_b"], ["d_head"],
                           ["e_stack"])]
    h = {k: jnp.float32(v) for k, v in opt.hypers.items()
         if isinstance(v, float)}
    adam = jax.jit(lambda p, g, m, v, h, model: mt.flat_adam(
        p, g, m, v, lr=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
        eps=h["eps"], weight_decay=h["weight_decay"], step=jnp.int32(1),
        grad_scale=jnp.float32(1.0), model_dtype=model), static_argnums=5)
    want = [adam(old[bi], flat_g[bi], old_m[bi], old_v[bi], h,
                 b.model_dtype if b.model_dtype != b.dtype else None)
            for bi, b in enumerate(opt._plan.buckets)]
    opt.step(grads)
    work = opt._master_bufs or opt._param_bufs
    assert [b.shape for b in work] == SHAPES
    for bi, w in enumerate(want):
        _same_bits([jnp.ravel(work[bi]),
                    jnp.ravel(opt.opt_state["exp_avg"][bi]),
                    jnp.ravel(opt.opt_state["exp_avg_sq"][bi])], w[:3])
        if len(w) == 4:
            _same_bits(jnp.ravel(opt._param_bufs[bi]), w[3])


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_one_leaf_buckets_match_per_leaf_path(name, dtype):
    cls, hypers = OPTS[name]
    dt = DTYPES[dtype]
    opt = _build(name, dt)
    ref = cls(_params(dt), fuse_buckets=False, **hypers)
    for i in range(3):
        grads = _grads(ref.params, 10 + i)
        _close(opt.step(grads), ref.step(grads), dt)
    _close(opt.state_dict()["state"], ref.state_dict()["state"], dt)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_handed_out_leaf_is_the_buffer_and_survives_steps(dtype):
    """With masters, ``params``'s one-leaf leaves ARE the packed
    model-dtype buffers (one copy between steps); the step neither
    reads nor donates them, so a caller may keep them."""
    opt = _build("adam", DTYPES[dtype])
    opt.step(_grads(opt.params, 1))
    held = opt.params
    masters = opt._master_bufs is not None
    shared = {id(opt._param_bufs[bi]) for bi in (0, 2, 3)}
    assert (shared <= {id(x) for x in jax.tree_util.tree_leaves(held)}) \
        == masters
    assert opt._shared == ((0, 2, 3) if masters else ())
    want = _bits(held)
    for i in range(3):
        opt.step(_grads(held, 2 + i), found_inf=jnp.int32(i == 1))
    _same_bits(held, want)


@pytest.mark.parametrize("then", ["packed_snapshot", "rechunk"])
def test_a_donated_handed_out_leaf_is_named_until_the_next_step(then):
    """``params`` must not be donated: with masters a one-leaf leaf is
    the optimizer's own buffer.  A caller program that donates it
    deletes it; the step, which does not read it, still runs, and what
    reads it says what happened until that step writes it anew."""
    opt = _build("adam", jnp.bfloat16)
    opt.step(_grads(opt.params, 1))
    doubled = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: x * 2, p),
                      donate_argnums=0)
    doubled(opt.params)
    assert opt._param_bufs[0].is_deleted()
    with pytest.raises(RuntimeError, match="donat"):
        getattr(opt, then)(*(() if then == "packed_snapshot" else (1024,)))
    opt.step(_grads(opt._params_cache, 2))
    assert not any(b.is_deleted() for b in opt._param_bufs)
    if then == "packed_snapshot":
        snap = opt.packed_snapshot()
        _same_bits(snap["param_bufs"], opt._param_bufs)
    else:
        assert opt.rechunk(1024)
        assert opt._plan.max_bucket_bytes == 1024


# ---- a list of leaves that has the structure of its buffers ----------------

def _listed():
    """Three (4, 4) leaves in a plain list, float32, bfloat16, float32,
    each alone in a bucket: the buckets come by dtype, so their order
    is leaf 0, leaf 2, leaf 1, and the tree and the packed buffers
    have the same structure and shapes."""
    params = [jnp.full((4, 4), 1.0, jnp.float32),
              jnp.full((4, 4), 1.0, jnp.bfloat16),
              jnp.full((4, 4), 1.0, jnp.float32)]
    grads = [jnp.full((4, 4), g, p.dtype)
             for g, p in zip((0.1, -0.2, 0.3), params)]
    # moments far apart, so a moment read for the wrong leaf shows
    state = {"exp_avg": [jnp.full((4, 4), m, jnp.float32)
                         for m in (0.5, 2.0, -2.0)],
             "exp_avg_sq": [jnp.full((4, 4), v, jnp.float32)
                            for v in (0.25, 4.0, 1e-2)]}
    return params, grads, state


def _listed_opt(params, fuse=True):
    opt = FusedAdam(params, lr=0.1, master_weights=False,
                    max_bucket_bytes=64, fuse_buckets=fuse)
    if fuse:
        assert [b.leaves[0].index for b in opt._plan.buckets] == [0, 2, 1]
    return opt


@pytest.mark.parametrize("given", ["per_leaf_state", "packed_state",
                                   "grad_list", "flat_grads"])
def test_a_list_tree_is_not_read_in_bucket_order(given):
    """The per-leaf reading and the packed one differ by the bucket
    order: a per-leaf state or gradient list is read per leaf, the
    optimizer's own packed state (a ``PackedState``) and a
    ``FlatGrads`` bundle's buffers in bucket order."""
    params, grads, state = _listed()
    ref = _listed_opt(params, fuse=False)
    opt = _listed_opt(params)
    step = jnp.int32(1)
    if given == "per_leaf_state":
        assert not opt._state_is_packed(state)
        got, new = opt.functional_step(params, state, grads, step)
        want, new_ref = ref.functional_step(params, state, grads, step)
        _close(new, new_ref, jnp.float32)
    elif given == "packed_state":
        ref.load_state_dict({**ref.state_dict(), "state": state})
        opt.load_state_dict({**opt.state_dict(), "state": state})
        packed = opt.opt_state
        assert opt._state_is_packed(packed)
        # the same buffers in a plain dict could be either: per leaf
        assert not opt._state_is_packed(dict(packed))
        got, new = opt.functional_step(params, packed, grads, step)
        assert type(new) is type(packed)
        want = ref.step(grads)
        _close(opt._plan.unpack_state_field(new["exp_avg"]),
               ref.opt_state["exp_avg"], jnp.float32)
    else:
        if given == "flat_grads":
            bufs = opt._plan.pack_grads(grads)
            assert [b.dtype for b in bufs] \
                == [jnp.float32, jnp.float32, jnp.bfloat16]
            grads = amp.FlatGrads(bufs=bufs, grad_norm=jnp.float32(1.0),
                                  found_inf=jnp.int32(0),
                                  clip_coef=jnp.float32(1.0))
        got = opt.step(grads)
        want = ref.step(_listed()[1])
    _close(got, want, jnp.bfloat16)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype


# ---- checkpoints -----------------------------------------------------------

@pytest.mark.parametrize("via", ["packed_snapshot", "checkpoint_v2"])
def test_a_flat_layout_snapshot_loads(via, tmp_path):
    """A snapshot whose one-leaf buckets are flat (the layout before
    they kept their leaf's shape) loads, and training resumes to the
    same bits."""
    opt = _build("adam", jnp.bfloat16)
    opt.step(_grads(opt.params, 1))
    if via == "packed_snapshot":
        snap = opt.packed_snapshot()
        flat = {k: [jnp.ravel(b) for b in snap[k]]
                for k in ("param_bufs", "master_bufs")}
        state = {k: [jnp.ravel(b) for b in v]
                 for k, v in snap["state"].items()}
    else:
        # the v2 payload is flat, as a flat-layout writer's was, and
        # the header's plan record is the same in both layouts
        path = str(tmp_path / "ckpt")
        checkpoint.save_training_state(path, optimizer=opt, step=1)
    resumed = _build("adam", jnp.bfloat16)
    if via == "packed_snapshot":
        resumed.load_packed_snapshot(snap["step"], snap["hypers"],
                                     flat["param_bufs"],
                                     flat["master_bufs"], state)
    else:
        adopted = []
        plain = resumed.load_packed_snapshot
        resumed.load_packed_snapshot = (
            lambda *a: adopted.append(1) or plain(*a))
        checkpoint.load_training_state(path, _params(jnp.bfloat16),
                                       optimizer=resumed)
        assert adopted                      # the packed fast path
    assert [b.shape for b in resumed._master_bufs] == SHAPES
    for i in range(2):
        grads = _grads(opt.params, 5 + i)
        opt.step(grads)
        resumed.step(grads)
    _same_bits((resumed._param_bufs, resumed._master_bufs,
                resumed.opt_state),
               (opt._param_bufs, opt._master_bufs, opt.opt_state))


# ---- the other users of the packed layout ----------------------------------

def _reduce(plan, decompose):
    mesh = comm.initialize(data=4, devices=jax.devices()[:4])
    try:
        g = jax.random.normal(jax.random.key(3), (4, 24, 16))

        def per_shard(gs):
            tree = _grads(_params(jnp.float32), 1)
            tree["a_emb"] = gs[0]
            bufs = all_reduce_flat_buffers(plan.pack_grads(tree),
                                           comm.AXIS_DATA,
                                           decompose=decompose)
            return [b[None] for b in bufs]

        got = jax.jit(comm.shard_map(
            per_shard, mesh, in_specs=P(comm.AXIS_DATA),
            out_specs=P(comm.AXIS_DATA)))(g)
    finally:
        comm.destroy()
    assert [b.shape[1:] for b in got] == SHAPES
    np.testing.assert_allclose(np.asarray(got[0][0]),
                               np.asarray(jnp.mean(g, 0)), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("user", ["rechunk", "fp8_slots", "fp8_grads",
                                  "ddp_psum", "ddp_reduce_scatter",
                                  "flat_pipeline", "clip_grad"])
def test_users_of_the_packed_layout_keep_working(user):
    if user == "rechunk":
        opt, ref = _build("adam", jnp.bfloat16), _build("adam", jnp.bfloat16)
        for i, cap in enumerate((None, CAP)):
            grads = _grads(opt.params, 20 + i)
            opt.step(grads)
            ref.step(grads)
            assert opt.rechunk(cap)
        assert [b.shape for b in opt._master_bufs] == SHAPES
        _same_bits(opt.opt_state, ref.opt_state)
        _same_bits(opt.params, ref.params)
        return
    if user == "fp8_slots":
        opt = _build("adam", jnp.float32)
        opt.enable_fp8(amp.Fp8Policy())
        opt.step(_grads(opt.params, 1))
        st = fp8.init_state(opt._plan, opt.fp8_policy)
        want, _ = fp8.update_state_ref(
            st, opt.params, opt._plan, opt.fp8_policy,
            fp8_max_value=opt.fp8_policy.fwd_max())
        _same_bits(opt.opt_state["fp8_scale"], want.scale)
        _same_bits(opt.opt_state["fp8_amax_history"], want.amax_history)
        return
    plan = BucketPlan.from_tree(_params(jnp.float32), max_bucket_bytes=CAP)
    grads = _grads(_params(jnp.float32), 4)
    if user == "fp8_grads":
        policy = amp.Fp8Policy()
        st = fp8.init_state(plan, policy)
        got, _ = fp8.update_state(st, plan.pack_grads(grads), plan, policy)
        want, _ = fp8.update_state_ref(st, grads, plan, policy)
        _same_bits((got.scale, got.amax_history),
                   (want.scale, want.amax_history))
    elif user in ("ddp_psum", "ddp_reduce_scatter"):
        _reduce(plan, user[4:])
    elif user == "flat_pipeline":
        opt = _build("sgd", jnp.float32)
        ref = _build("sgd", jnp.float32)
        pipe = FlatGradPipeline(optimizer=opt, max_grad_norm=0.5)
        acc = pipe.accumulate(pipe.init_accum(), grads)
        assert [b.shape for b in acc.bufs] == SHAPES
        flat = pipe.finalize(acc, inv_scale=1.0)
        assert [b.shape for b in flat.bufs] == SHAPES
        pipe.step(flat)
        ref.step(grads, clip_coef=flat.clip_coef)
        _same_bits(opt.params, ref.params)
    else:
        bufs = plan.pack_grads(grads)
        got, norm = clip_grad_norm(bufs, 0.1)
        want, want_norm = clip_grad_norm(grads, 0.1)
        assert [b.shape for b in got] == SHAPES
        np.testing.assert_allclose(float(norm), float(want_norm),
                                   rtol=1e-6)
        _close(plan.unpack_grads(got), want, jnp.float32)


# ---- the counters ----------------------------------------------------------

def test_leaf_shaped_counters_at_construction():
    seen = []

    def sink(name, value):
        seen.append((name, value))
    hostmetrics.add_sink(sink)
    try:
        _build("adam", jnp.bfloat16)
        FusedAdam(_params(jnp.bfloat16))            # one bucket of five
    finally:
        hostmetrics.remove_sink(sink)
    got = [(n, v) for n, v in seen if n.startswith("optim/leaf_shaped")]
    assert got == [("optim/leaf_shaped_buckets", 3.0),
                   ("optim/leaf_shaped_params", 798.0),
                   ("optim/leaf_shaped_buckets", 0.0),
                   ("optim/leaf_shaped_params", 0.0)]
