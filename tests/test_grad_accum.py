"""Interleaved per-bucket collectives + fused flat gradient
accumulation (ISSUE 10).

Two families:

* **Overlap schedule** — the reduce-in-backward seam
  (``FlatGradPipeline(interleave=True)``) is bitwise identical to the
  trailing schedule under an 8-way shard_map, the reduce-scatter +
  all-gather decomposition matches the plain psum, chunked plans
  (``max_bucket_bytes``) round-trip, and the
  ``interleaved_collectives`` dependency-cone checker separates the
  interleaved program from the trailing pathology (so the apexverify
  spec has teeth).

* **Flat accumulation** — ``microbatches=N`` is bit-exact against the
  equivalent single-batch step for all five fused optimizers (exact
  dyadic-rational test data, f32 AND bf16+masters), found_inf latches
  across microbatches, the accumulator zeroes on step commit, donated
  accumulator buffers survive ``state_dict`` snapshots, and the
  accumulation loop's scan body structurally contains one bucket pack
  + one fused add per bucket and ZERO per-leaf unpacking.

Suite ``run_amp`` in tests/run_test.py.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, comm
from apex_tpu.lint.semantic import jaxprs
from apex_tpu.multi_tensor_apply.packer import BucketPlan
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)

tree_map = jax.tree_util.tree_map
tree_leaves = jax.tree_util.tree_leaves

OPTS = [
    (FusedAdam, {}),
    (FusedSGD, {"momentum": 0.9}),
    (FusedAdagrad, {}),
    (FusedNovoGrad, {}),
    (FusedLAMB, {}),
]


def _exact_params(dtype=jnp.float32, layers=3):
    """Small-integer params: every value a dyadic rational with few
    mantissa bits, so sums/means over power-of-two batch sizes are
    EXACT in f32 (and bf16) — the substrate of the bit-exactness
    claims below."""
    rng = np.random.default_rng(0)
    return {
        f"l{i}": {
            "w": jnp.asarray(rng.integers(-2, 3, (8, 8)), dtype) * 0.5,
            "b": jnp.asarray(rng.integers(-1, 2, (8,)), dtype) * 0.5,
        }
        for i in range(layers)
    }


def _exact_batch(b=8):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-2, 3, (b, 8)), jnp.float32)
    y = jnp.asarray(rng.integers(-1, 2, (b, 8)), jnp.float32)
    return x, y


def _quad_loss(p, x, y):
    """Linear tower + quadratic loss: exact arithmetic on the integer
    data above (no transcendental rounds anything)."""
    h = x
    for k in sorted(p):
        h = h @ p[k]["w"].astype(jnp.float32) \
            + p[k]["b"].astype(jnp.float32)
    return jnp.mean((h - y) ** 2)


def _assert_trees_equal(a, b):
    for la, lb in zip(tree_leaves(a), tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# flat_accumulate kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_flat_accumulate_matches_ref_and_oracle(gdtype):
    rng = np.random.default_rng(2)
    acc = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    g = jnp.asarray(rng.standard_normal(1000), jnp.float32).astype(gdtype)
    out_k, flag_k = mt.flat_accumulate(acc, g, scale=0.5)
    out_r, flag_r = mt.flat_accumulate_ref(acc, g, scale=0.5)
    oracle = acc + g.astype(jnp.float32) * 0.5
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(oracle),
                               rtol=1e-6, atol=0)
    assert int(flag_k) == 0 == int(flag_r)
    assert out_k.dtype == jnp.float32


def test_flat_accumulate_flags_nonfinite_result():
    acc = jnp.zeros((8,), jnp.float32)
    g = jnp.zeros((8,), jnp.float32).at[3].set(jnp.inf)
    out, flag = mt.flat_accumulate(acc, g)
    assert int(flag) == 1
    # inf - inf through a later add -> nan: still flagged
    out2, flag2 = mt.flat_accumulate(out, -g)
    assert int(flag2) == 1 and not np.isfinite(np.asarray(out2)[3])


def test_flat_accumulate_rejects_non_f32_accumulator():
    with pytest.raises(ValueError, match="f32"):
        mt.flat_accumulate(jnp.zeros((8,), jnp.bfloat16),
                           jnp.zeros((8,), jnp.bfloat16))
    with pytest.raises(ValueError, match="f32"):
        mt.flat_accumulate_ref(jnp.zeros((8,), jnp.bfloat16),
                               jnp.zeros((8,), jnp.bfloat16))


# ---------------------------------------------------------------------------
# microbatches=N: bit-exact parity vs the single-batch step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls,kw", OPTS,
                         ids=[c.__name__ for c, _ in OPTS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16_masters"])
def test_microbatched_step_bit_exact_vs_single_batch(cls, kw, dtype):
    """The acceptance claim: a microbatches=N flat-accumulated step is
    BIT-EXACT against the equivalent single-large-batch step, for all
    five fused optimizers, f32 and bf16+masters.  Exact dyadic data
    makes every sum/mean exact, so the two summation orders agree to
    the bit; the optimizer then sees bit-identical gradients.  The
    bf16 case uses a single-layer model with magnitudes chosen so
    every cotangent fits bf16's 8 mantissa bits (exact in BOTH
    precisions); f32 runs the deeper tower."""
    if dtype == jnp.bfloat16:
        w0 = jnp.asarray(np.random.default_rng(3).integers(
            -1, 2, (8, 8)), dtype) * 0.5
        mk = lambda: {"head": {"w": w0, "b": jnp.zeros((8,), dtype)}}
        x = jnp.asarray(np.random.default_rng(4).integers(
            -1, 2, (8, 8)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(5).integers(
            0, 2, (8, 8)), jnp.float32)
    else:
        x, y = _exact_batch(8)
        mk = lambda: _exact_params(dtype)
    scaler = amp.LossScaleState.create(2.0 ** 8)   # power of two: exact

    results = {}
    for mode in ("single", "micro"):
        params = mk()
        opt = cls(params, lr=0.25, **kw)
        pipe = amp.FlatGradPipeline(optimizer=opt)
        loss, flat = pipe.scaled_value_and_grad(
            _quad_loss, scaler, params, x, y,
            microbatches=4 if mode == "micro" else 1)
        new_p = opt.step(flat, found_inf=flat.found_inf)
        results[mode] = (loss, flat, new_p)

    l1, f1, p1 = results["single"]
    l2, f2, p2 = results["micro"]
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(f1.grad_norm),
                                  np.asarray(f2.grad_norm))
    # gradient buffers: micro accumulates in f32; the single-batch
    # buffers (model dtype) must match exactly after the same upcast
    for b1, b2 in zip(f1.bufs, f2.bufs):
        np.testing.assert_array_equal(
            np.asarray(b1, np.float32), np.asarray(b2, np.float32))
    _assert_trees_equal(p1, p2)


def test_microbatched_flat_matches_per_leaf_oracle_bit_exact():
    """grads_layout='flat' microbatch accumulation == the per-leaf
    tree oracle, bit for bit (same adds in the same order, packed vs
    unpacked), on ARBITRARY (non-exact) data."""
    params = {f"l{i}": {"w": jax.random.normal(jax.random.key(i),
                                               (8, 8)) * 0.3,
                        "b": jnp.zeros((8,))} for i in range(3)}
    x = jax.random.normal(jax.random.key(9), (8, 8))
    y = jax.random.normal(jax.random.key(10), (8, 8))
    scaler = amp.LossScaleState.create(2.0 ** 10)

    loss_t, grads_t, fi_t = amp.scaled_value_and_grad(
        _quad_loss, scaler, params, x, y, microbatches=4)
    loss_f, flat, fi_f = amp.scaled_value_and_grad(
        _quad_loss, scaler, params, x, y, microbatches=4,
        grads_layout="flat")
    plan = BucketPlan.from_tree(params)
    np.testing.assert_array_equal(np.asarray(loss_t), np.asarray(loss_f))
    assert int(fi_t) == int(fi_f) == 0
    packed_oracle = plan.pack(
        tree_map(lambda g: g.astype(jnp.float32), grads_t))
    for b_o, b_f in zip(packed_oracle, flat.bufs):
        np.testing.assert_array_equal(np.asarray(b_o), np.asarray(b_f))


def test_microbatched_has_aux_and_error_paths():
    params = _exact_params()
    x, y = _exact_batch(8)
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt)

    def loss_aux(p, x, y):
        return _quad_loss(p, x, y), jnp.sum(x)

    (loss, aux), flat = pipe.scaled_value_and_grad(
        loss_aux, scaler, params, x, y, has_aux=True, microbatches=4)
    assert aux.shape == (4,)        # stacked along the microbatch axis
    with pytest.raises(ValueError, match="divide"):
        pipe.scaled_value_and_grad(_quad_loss, scaler, params,
                                   x[:6], y[:6], microbatches=4)
    with pytest.raises(ValueError, match="batch arguments"):
        pipe.scaled_value_and_grad(lambda p: jnp.float32(0.0), scaler,
                                   params, microbatches=4)
    # mismatched leading dims (a non-batch positional arg) must raise
    # clearly, never silently mis-split
    with pytest.raises(ValueError, match="leading"):
        pipe.scaled_value_and_grad(
            lambda p, xx, m: _quad_loss(p, xx, xx * 0) + jnp.sum(m),
            scaler, params, x, jnp.ones((2, 3)), microbatches=4)
    with pytest.raises(ValueError, match="leading"):
        pipe.scaled_value_and_grad(
            lambda p, xx, s: _quad_loss(p, xx, xx * 0) * s,
            scaler, params, x, jnp.float32(2.0), microbatches=4)


# ---------------------------------------------------------------------------
# found_inf latching + branch-free skip across microbatches
# ---------------------------------------------------------------------------

def test_one_bad_microbatch_latches_and_skips_the_whole_step():
    params = _exact_params()
    x, y = _exact_batch(8)
    # poison ONLY microbatch 2 (rows 4..5)
    x_bad = x.at[4, 0].set(jnp.inf)
    scaler = amp.LossScaleState.create(2.0 ** 8)
    opt = FusedAdam(params, lr=0.25)
    pipe = amp.FlatGradPipeline(optimizer=opt)
    p_before = jax.device_get(opt.params)
    step_before = int(opt.step_count)

    loss, flat = pipe.scaled_value_and_grad(
        _quad_loss, scaler, params, x_bad, y, microbatches=4)
    assert int(flat.found_inf) == 1
    # clip coefficient pinned neutral on overflow (never 0 or NaN)
    assert float(flat.clip_coef) == 1.0

    opt.step(flat, found_inf=flat.found_inf)
    _assert_trees_equal(p_before, jax.device_get(opt.params))
    assert int(opt.step_count) == step_before   # clock held too


def test_accumulate_latch_is_sticky_across_later_clean_microbatches():
    params = _exact_params()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt)
    good = tree_map(jnp.ones_like, params)
    bad = tree_map(lambda p: jnp.full(p.shape, jnp.nan), params)
    acc = pipe.init_accum()
    acc = pipe.accumulate(acc, good)
    assert int(acc.found_inf) == 0
    acc = pipe.accumulate(acc, bad)
    assert int(acc.found_inf) == 1
    acc = pipe.accumulate(acc, good)       # a later clean microbatch
    assert int(acc.found_inf) == 1         # cannot clear the latch
    flat = pipe.finalize(acc, inv_scale=1.0)
    assert int(flat.found_inf) == 1
    assert int(acc.count) == 3


# ---------------------------------------------------------------------------
# accumulator lifecycle: zeroing on commit, donation vs state_dict
# ---------------------------------------------------------------------------

def test_accumulator_zeroing_on_step_commit():
    params = _exact_params()
    x, y = _exact_batch(8)
    scaler = amp.LossScaleState.create(2.0 ** 8)
    opt = FusedAdam(params, lr=0.25)
    pipe = amp.FlatGradPipeline(optimizer=opt)

    def one_window(acc):
        for i in range(4):
            _, g = jax.value_and_grad(
                lambda p: _quad_loss(p, x[2 * i:2 * i + 2],
                                     y[2 * i:2 * i + 2])
                * scaler.loss_scale)(params)
            acc = pipe.accumulate(acc, g)
        return acc

    acc = one_window(pipe.init_accum())
    flat1 = pipe.finalize(acc, scaler)
    acc = pipe.reset_accum(acc)            # step commit zeroes
    assert int(acc.count) == 0 and int(acc.found_inf) == 0
    for b in acc.bufs:
        assert not np.asarray(b).any()
    # the reused (zeroed) accumulator reproduces a fresh one bitwise
    flat2 = pipe.finalize(one_window(acc), scaler)
    for b1, b2 in zip(flat1.bufs, flat2.bufs):
        np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


def test_donated_accumulator_survives_state_dict_snapshots():
    """The accumulation step donates its GradAccum (the fused add is
    in place); an optimizer state_dict snapshot taken mid-window must
    stay readable through later donating accumulates AND through the
    committed (donating) optimizer step."""
    params = _exact_params()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt)
    grads = tree_map(jnp.ones_like, params)

    accum_jit = jax.jit(pipe.accumulate, donate_argnums=(0,))
    acc = accum_jit(opt.grad_accum_init(), grads)
    sd = opt.state_dict()                  # snapshot mid-accumulation
    acc = accum_jit(acc, grads)            # first acc donated away
    flat = pipe.finalize(acc, inv_scale=0.5)
    opt.step(flat, found_inf=flat.found_inf)   # donates opt_state
    # the snapshot is still fully materializable and loadable
    for leaf in tree_leaves(sd["state"]):
        np.asarray(leaf)
    opt2 = FusedAdam(params, lr=1e-3)
    opt2.load_state_dict(sd)
    assert int(opt2.step_count) == 0


# ---------------------------------------------------------------------------
# structural: the accumulation loop never unpacks per leaf
# ---------------------------------------------------------------------------

def test_scan_body_packs_per_bucket_and_never_unpacks():
    """Zero per-leaf work in the accumulation loop, asserted on the
    jaxpr: the scan body holds exactly one bucket-sized concatenate
    per bucket (the pack), one fused accumulate per bucket, and NO
    slice out of a bucket-sized buffer (the unpack signature)."""
    from apex_tpu.ops._dispatch import op_enabled

    params = _exact_params()
    x, y = _exact_batch(8)
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    plan = opt._plan
    nb = len(plan.buckets)
    pipe = amp.FlatGradPipeline(optimizer=opt)

    def micro_step(params, x, y):
        loss, flat = pipe.scaled_value_and_grad(
            _quad_loss, scaler, params, x, y, microbatches=4)
        return loss, flat.bufs

    jaxpr = jax.make_jaxpr(micro_step)(params, x, y)
    scans = [e for e in jaxprs.iter_eqns(jaxpr)
             if e.primitive.name == "scan"]
    assert scans, "microbatches=N must lower to a scan"
    body = scans[0].params["jaxpr"]
    bucket_sizes = {(b.size,) for b in plan.buckets}
    packs = [s for s in jaxprs.concat_out_shapes(body)
             if s in bucket_sizes]
    assert len(packs) == nb
    # no per-leaf unpack: nothing slices a bucket-sized buffer apart
    bad = [e for e in jaxprs.iter_eqns(body)
           if e.primitive.name == "slice"
           and tuple(getattr(e.invars[0].aval, "shape", ()))
           in bucket_sizes]
    assert not bad, [str(e) for e in bad]
    if op_enabled("multi_tensor"):
        counts = jaxprs.primitive_counts(body)
        assert counts.get("pallas_call", 0) == nb   # flat_accumulate
    # and the registered spec pins the donated-accumulator aliasing
    from apex_tpu.lint import semantic
    res = semantic.verify_spec(
        semantic.get_spec("amp.flat_accumulate_step"))
    assert res.ok, res.failures
    assert "donated_aliases_min" in res.checked


# ---------------------------------------------------------------------------
# overlap schedule: interleave seam, decomposition, chunked plans
# ---------------------------------------------------------------------------

def _dp_step(pipe, scaler, mesh):
    def f(p, x, y):
        loss, flat = pipe.scaled_value_and_grad(_quad_loss, scaler,
                                                p, x, y)
        return loss, flat.bufs, flat.grad_norm
    # interleaved vs trailing are two different programs by design —
    # each comparison leg compiles exactly once
    # apexlint: disable-next=APX302
    return jax.jit(comm.shard_map(
        f, mesh, in_specs=(P(), P(comm.AXIS_DATA), P(comm.AXIS_DATA)),
        out_specs=P()))


def test_interleaved_schedule_bitwise_matches_trailing():
    mesh = comm.initialize(data=8)
    try:
        params = _exact_params()
        scaler = amp.LossScaleState.create(2.0 ** 8)
        opt = FusedAdam(params, lr=1e-3, max_bucket_bytes=300)
        assert len(opt._plan.buckets) == 3
        x = jax.random.normal(jax.random.key(5), (16, 8))
        y = jax.random.normal(jax.random.key(6), (16, 8))
        outs = {}
        for name, interleave in (("trail", False), ("seam", True)):
            pipe = amp.FlatGradPipeline(
                optimizer=opt, max_grad_norm=1.0,
                axis_name=comm.AXIS_DATA, interleave=interleave)
            outs[name] = _dp_step(pipe, scaler, mesh)(params, x, y)
        for a, b in zip(outs["trail"][1], outs["seam"][1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(outs["trail"][2]),
                                      np.asarray(outs["seam"][2]))
    finally:
        comm.destroy()


def test_reduce_scatter_decomposition_matches_psum():
    mesh = comm.initialize(data=8)
    try:
        params = _exact_params()
        scaler = amp.LossScaleState.create(2.0 ** 8)
        # deliberately indivisible bucket sizes (72 elems vs 8 ranks
        # pads to 72? 72 % 8 == 0 — use the monolithic 216-elem plan,
        # 216 % 8 == 0 too; chunk at one leaf per bucket to get a
        # 64-elem w and an 8-elem b... all divisible; force padding
        # with a 3-layer + extra 5-elem leaf tree)
        params["odd"] = {"w": jnp.ones((5, 1), jnp.float32),
                         "b": jnp.zeros((3,), jnp.float32)}
        opt = FusedAdam(params, lr=1e-3, max_bucket_bytes=300)
        x = jax.random.normal(jax.random.key(7), (16, 8))
        y = jax.random.normal(jax.random.key(8), (16, 8))

        def loss_fn(p, x, y):
            base = {k: v for k, v in p.items() if k != "odd"}
            return _quad_loss(base, x, y) \
                + jnp.sum(p["odd"]["w"] ** 2) \
                + jnp.sum(p["odd"]["b"] ** 2)

        outs = {}
        for dec in ("psum", "reduce_scatter"):
            pipe = amp.FlatGradPipeline(
                optimizer=opt, axis_name=comm.AXIS_DATA,
                reduce_decompose=dec)

            def f(p, x, y, pipe=pipe):
                loss, flat = pipe.scaled_value_and_grad(
                    loss_fn, scaler, p, x, y)
                return flat.bufs
            # psum vs reduce_scatter are two programs by design
            # apexlint: disable-next=APX302
            outs[dec] = jax.jit(comm.shard_map(
                f, mesh,
                in_specs=(P(), P(comm.AXIS_DATA), P(comm.AXIS_DATA)),
                out_specs=P()))(params, x, y)
        for a, b in zip(outs["psum"], outs["reduce_scatter"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
    finally:
        comm.destroy()


def test_always_fp32_composes_with_packed_path_without_double_cast():
    from apex_tpu.parallel.distributed import all_reduce_flat_buffers
    mesh = comm.initialize(data=8)
    try:
        bufs = (jnp.ones((256,), jnp.bfloat16),
                jnp.ones((128,), jnp.float32))

        def reduce(bufs):
            return tuple(all_reduce_flat_buffers(
                list(bufs), comm.AXIS_DATA, always_fp32=True))

        fn = comm.shard_map(reduce, mesh, in_specs=(P(),),
                            out_specs=P())
        out = jax.jit(fn)(bufs)
        assert all(b.dtype == jnp.float32 for b in out)
        # exactly ONE convert (bf16 bucket in): the f32 bucket pays
        # zero converts, and nothing casts back after the psum
        jaxpr = jax.make_jaxpr(fn)(bufs)
        converts = [e for e in jaxprs.iter_eqns(jaxpr)
                    if e.primitive.name == "convert_element_type"]
        assert len(converts) == 1, [str(e) for e in converts]
        # average=True over 8 replicated ranks of ones -> exactly 1.0
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.ones((128,), np.float32))
    finally:
        comm.destroy()


def test_chunked_plan_roundtrip_and_state_dict():
    params = _exact_params()
    n_elems = sum(int(l.size) for l in tree_leaves(params))
    plan = BucketPlan.from_tree(params, max_bucket_bytes=300)
    assert len(plan.buckets) == 3
    assert sum(b.size for b in plan.buckets) == n_elems
    tree = plan.unpack(plan.pack_work(params))
    _assert_trees_equal(tree, params)
    # a chunked optimizer interloads checkpoints with a monolithic one
    grads = tree_map(jnp.ones_like, params)
    opt_c = FusedAdam(params, lr=0.25, max_bucket_bytes=300)
    opt_m = FusedAdam(params, lr=0.25)
    opt_c.step(grads)
    opt_m.load_state_dict(opt_c.state_dict())
    opt_m.params = opt_c.params
    p_c = opt_c.step(grads)
    p_m = opt_m.step(grads)
    _assert_trees_equal(p_c, p_m)


def test_pipeline_rejects_conflicting_max_bucket_bytes():
    """A supplied plan (optimizer=/plan=) wins over later derivation,
    so a mismatching chunking request must raise — silently keeping
    the optimizer's monolithic plan would degrade interleave=True to
    the trailing schedule it exists to replace."""
    params = _exact_params()
    opt = FusedAdam(params, lr=1e-3)               # monolithic plan
    with pytest.raises(ValueError, match="max_bucket_bytes"):
        amp.FlatGradPipeline(optimizer=opt, max_bucket_bytes=300,
                             interleave=True)
    # matching cap (or none at all) composes fine
    opt_c = FusedAdam(params, lr=1e-3, max_bucket_bytes=300)
    amp.FlatGradPipeline(optimizer=opt_c, max_bucket_bytes=300)
    amp.FlatGradPipeline(optimizer=opt_c)


def test_interleaved_cone_checker_separates_trailing_schedule():
    """The apexverify overlap invariant has teeth: the SAME checker
    that passes the chunked+seam program fails the monolithic trailing
    program."""
    from apex_tpu.lint.semantic.registry import (
        _chk_interleaved_collectives)

    mesh = comm.initialize(data=8)
    try:
        params = _exact_params()
        scaler = amp.LossScaleState.create()
        x = jax.random.normal(jax.random.key(11), (16, 8))
        y = jax.random.normal(jax.random.key(12), (16, 8))

        def jaxpr_of(opt, interleave):
            pipe = amp.FlatGradPipeline(
                optimizer=opt, axis_name=comm.AXIS_DATA,
                interleave=interleave)

            def f(p, x, y):
                loss, flat = pipe.scaled_value_and_grad(
                    _quad_loss, scaler, p, x, y)
                return loss, flat.bufs
            return jax.make_jaxpr(comm.shard_map(
                f, mesh,
                in_specs=(P(), P(comm.AXIS_DATA), P(comm.AXIS_DATA)),
                out_specs=P()))(params, x, y)

        good = jaxpr_of(FusedAdam(params, lr=1e-3,
                                  max_bucket_bytes=300), True)
        bad = jaxpr_of(FusedAdam(params, lr=1e-3), False)
        expect = {"min_collectives": 2}
        assert _chk_interleaved_collectives({"jaxpr": good},
                                            expect) is None
        msg = _chk_interleaved_collectives({"jaxpr": bad}, expect)
        assert msg is not None and "collective" in msg

        # and the dependency cones behind the verdicts are as
        # documented: proper, pairwise-distinct SET subsets
        scopes = jaxprs.collective_compute_cones(good)
        scope = max(scopes, key=lambda s: len(s["collectives"]))
        colls = scope["collectives"]
        assert len(colls) == 3
        assert len({c["cone"] for c in colls}) == 3
        assert min(c["cone_compute"] for c in colls) \
            < scope["total_compute"]
    finally:
        comm.destroy()


def test_registered_overlap_and_accum_specs_pass():
    from apex_tpu.lint import semantic
    res = semantic.verify_spec(
        semantic.get_spec("amp.interleaved_flat_step"))
    assert res.ok, res.failures
    assert {"interleaved_collectives", "donated_aliases_min",
            "psum_count", "no_host_transfer"} <= set(res.checked)
    assert len(semantic.verify_all()) >= 18


# ---------------------------------------------------------------------------
# platform: latency-hiding-scheduler flag wiring (provenance)
# ---------------------------------------------------------------------------

def test_lhs_flags_withheld_unless_tpu_target(monkeypatch):
    from apex_tpu import platform
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    prov = platform.enable_latency_hiding_scheduler()
    assert prov["applied"] is False
    assert prov["xla_flags_added"] == []
    assert "not tpu" in prov["reason"]
    assert platform.latency_hiding_provenance() == prov
    # no platform selection at all (the common non-TPU machine):
    # withheld too — "default" must never get TPU-only XLA_FLAGS that
    # a non-TPU backend could reject at init
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    prov = platform.enable_latency_hiding_scheduler()
    assert prov["applied"] is False and prov["xla_flags_added"] == []
    assert prov["target"] == "default"


def test_lhs_flags_appended_idempotently_for_tpu_target(monkeypatch):
    import warnings

    from apex_tpu import platform
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_something_else=1")
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    with warnings.catch_warnings():
        # the backend is already up in this test process: the call
        # must WARN and record applied=False, never half-configure
        warnings.simplefilter("error")
        with pytest.raises(RuntimeWarning, match="backend"):
            platform.enable_latency_hiding_scheduler()
        warnings.simplefilter("ignore")
        prov = platform.enable_latency_hiding_scheduler()
    assert prov["applied"] is False        # backend already initialized
    assert any("latency_hiding" in f for f in prov["xla_flags_added"])
    assert any("async_collective" in f
               for f in prov["libtpu_flags_added"])
    assert "--xla_something_else=1" in os.environ["XLA_FLAGS"]
    # idempotent: a second call adds nothing, records skips
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        prov2 = platform.enable_latency_hiding_scheduler()
    assert prov2["xla_flags_added"] == []
    assert prov2["libtpu_flags_added"] == []
    assert len(prov2["skipped"]) == (
        len(prov["xla_flags_added"]) + len(prov["libtpu_flags_added"]))


# ---------------------------------------------------------------------------
# bench harness smoke (tier-1 keeps the tooling runnable)
# ---------------------------------------------------------------------------

def test_flat_accumulate_microbench_smoke():
    """Harness smoke + the acceptance floor on what a loaded CPU
    cannot move: at a many-leaf shape one accumulation traces to at
    least an op per leaf on the per-leaf side and to a handful per
    bucket on the flat side (the wall-clock ratio of two CPU timings
    is printed by the bench, not asserted: six test workers share the
    machine)."""
    from apex_tpu.optimizers.bucketing_bench import bench_flat_accumulate
    r = bench_flat_accumulate(layers=32, hidden=16, iters=3, reps=2)
    assert r["accum_per_leaf_ms"] > 0
    assert r["accum_flat_ms"] > 0
    assert r["accum_leaves"] == 128
    assert r["accum_per_leaf_ops"] >= r["accum_leaves"], r
    assert r["accum_per_leaf_ops"] >= 1.3 * r["accum_flat_ops"], r


def test_grad_accum_train_bench_smoke():
    from apex_tpu.optimizers.bucketing_bench import bench_grad_accum
    r = bench_grad_accum(layers=2, hidden=16, batch=8,
                         n_micro=(1, 2), iters=2, reps=1)
    for n in (1, 2):
        assert r[f"grad_accum_flat_n{n}_ms"] > 0
        assert r[f"grad_accum_per_leaf_n{n}_ms"] > 0


def test_overlap_schedule_bench_smoke():
    """bench.py's interleaved-vs-trailing observatory leg runs end to
    end off-TPU (capture -> attribute -> overlap_pct both ways); the
    hardware target rides BENCH rounds + the perf_gate budget row."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    r = bench.bench_overlap_schedule(jax, jnp, steps=3, layers=3,
                                     hidden=32)
    assert r["overlap_buckets"] >= 2
    for leg in ("interleaved", "trailing"):
        assert r.get(f"overlap_{leg}_pct") is not None
