"""fp8 training: delayed-scaling bookkeeping, packed state round
trips, overflow latching, watchdog rollback, and the amp.fp8_step
spec (ISSUE 13 acceptance).

The delayed-scaling state transition must be BIT-EXACT across every
layout that computes it: the packed per-bucket pass
(``ops.multi_tensor.flat_amax_scale_update``), its scatter-max
oracle, and the per-leaf tree-walk oracle (``amp.fp8.
update_state_ref``) — and independent of the COMPUTE path (real fp8
dots vs the bf16-compute fallback CPU tier-1 runs).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.amp import fp8
from apex_tpu.fused_dense import (FusedDense, fp8_matmul,
                                  fused_dense_function)
from apex_tpu.multi_tensor_apply.packer import BucketPlan, cached_plan
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import FusedAdam, FusedSGD


def _tree(key=0, bf16=False):
    k = jax.random.key(key)
    ks = jax.random.split(k, 3)
    dt = jnp.bfloat16 if bf16 else jnp.float32
    return {
        "w": jax.random.normal(ks[0], (16, 16), dt) * 3.0,
        "b": jax.random.normal(ks[1], (16,), dt) * 0.01,
        "s": jax.random.normal(ks[2], (4, 4), dt) * 100.0,
    }


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------
# bookkeeping bit-exactness
# ---------------------------------------------------------------------

@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_amax_scale_update_kernel_vs_ref_bit_exact(bf16):
    tree = _tree(bf16=bf16)
    plan = cached_plan(tree)
    bufs = plan.pack_grads(tree)
    for bi, buf in enumerate(bufs):
        n = len(plan.segment_sizes(bi))
        hist = jnp.abs(jax.random.normal(jax.random.key(bi),
                                         (n, 5))).astype(jnp.float32)
        scale = jnp.ones((n,), jnp.float32) * 7.0
        kw = dict(fp8_max=448.0, margin=1.0, backoff_factor=0.5)
        h1, s1, f1 = mt.flat_amax_scale_update(
            buf, plan.segment_sizes(bi), hist, scale, **kw)
        h2, s2, f2 = mt.flat_amax_scale_update_ref(
            buf, plan.segment_sizes(bi), hist, scale, **kw)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        assert int(f1) == int(f2) == 0


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_packed_update_vs_per_leaf_oracle_bit_exact(bf16):
    """Multi-step delayed-scaling trajectory: the packed per-bucket
    pass equals the per-leaf tree-walk oracle bit for bit."""
    policy = fp8.Fp8Policy(amax_history_len=3, interval=2, margin=1.0)
    tree = _tree(bf16=bf16)
    plan = cached_plan(tree)
    st_a = fp8.init_state(plan, policy)
    st_b = fp8.init_state(plan, policy)
    for i in range(5):
        t = jax.tree_util.tree_map(lambda x: x * (1.0 + i), tree)
        bufs = plan.pack_grads(t)
        st_a, fa = fp8.update_state(st_a, bufs, plan, policy)
        st_b, fb = fp8.update_state_ref(st_b, t, plan, policy)
        assert int(fa) == int(fb) == 0
        _assert_trees_equal(st_a.amax_history, st_b.amax_history)
        _assert_trees_equal(st_a.scale, st_b.scale)


def test_interval_cadence_holds_updates():
    policy = fp8.Fp8Policy(amax_history_len=2, interval=3)
    tree = _tree()
    plan = cached_plan(tree)
    bufs = plan.pack_grads(tree)
    st = fp8.init_state(plan, policy)
    st1, _ = fp8.update_state(st, bufs, plan, policy)    # step 0: updates
    st2, _ = fp8.update_state(st1, bufs, plan, policy)   # step 1: holds
    st3, _ = fp8.update_state(st2, bufs, plan, policy)   # step 2: holds
    _assert_trees_equal(st1.scale, st2.scale)
    _assert_trees_equal(st2.amax_history, st3.amax_history)
    assert int(st3.step) == 3
    st4, _ = fp8.update_state(st3, bufs, plan, policy)   # step 3: updates
    assert float(jnp.max(st4.amax_history[0][:, 1])) > 0.0


def test_bookkeeping_identical_across_compute_modes():
    """The bf16-compute oracle contract: a whole fp8 train step under
    compute="bf16" carries EXACTLY the same scale bookkeeping as
    compute="fp8" given the same inputs (on CPU the compute paths
    also agree numerically, so the full state matches bitwise)."""
    states = {}
    for compute in ("fp8", "bf16"):
        policy = fp8.Fp8Policy(amax_history_len=4, compute=compute)
        params = _tree(key=3)
        opt = FusedAdam(params, lr=1e-2)
        opt.enable_fp8(policy)
        pipe = amp.FlatGradPipeline(optimizer=opt, fp8=policy)
        f8 = pipe.fp8_init()
        scaler = amp.LossScaleState.create(2.0 ** 4)
        x = jax.random.normal(jax.random.key(5), (4, 16))

        def loss(p, scales, x):
            h = jnp.tanh(fp8_matmul(x, p["w"], policy=policy,
                                    w_scale=scales["w"]) + p["b"])
            return jnp.mean(h ** 2) + jnp.mean(
                p["s"].astype(jnp.float32) ** 2)

        for _ in range(3):
            scales = opt.fp8_scales()
            _, flat, f8 = pipe.scaled_value_and_grad(
                loss, scaler, opt.params, scales, x, fp8_state=f8)
            opt.step(flat)
        states[compute] = (opt.opt_state["fp8_scale"],
                           opt.opt_state["fp8_amax_history"],
                           f8.scale, f8.amax_history)
    for a, b in zip(states["fp8"], states["bf16"]):
        _assert_trees_equal(a, b)


# ---------------------------------------------------------------------
# fp8_matmul numerics
# ---------------------------------------------------------------------

def test_fp8_matmul_matches_quantize_dequant_oracle():
    x = jax.random.normal(jax.random.key(0), (8, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (16, 4),
                          jnp.float32) * 0.1
    sx, sw = jnp.float32(16.0), jnp.float32(128.0)
    policy = fp8.Fp8Policy()
    y = fp8_matmul(x, w, policy=policy, x_scale=sx, w_scale=sw)
    qx = fp8.quantize(x, sx, "e4m3").astype(jnp.float32)
    qw = fp8.quantize(w, sw, "e4m3").astype(jnp.float32)
    ref = (qx @ qw) / (sx * sw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fp8_matmul_grad_is_quantized_and_typed():
    x = jax.random.normal(jax.random.key(0), (8, 16), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (16, 4),
                          jnp.bfloat16) * 0.1
    policy = fp8.Fp8Policy()

    def loss(x, w):
        return jnp.sum(fp8_matmul(x, w, policy=policy
                                  ).astype(jnp.float32) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert gx.dtype == x.dtype and gx.shape == x.shape
    assert gw.dtype == w.dtype and gw.shape == w.shape
    assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))
    # exactly 2 e4m3 + 1 e5m2 quantize converts in fwd+bwd
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    from apex_tpu.lint.semantic.jaxprs import fp8_convert_counts
    assert fp8_convert_counts(jaxpr) == {"e4m3": 2, "e5m2": 1}


def test_quantize_saturates_and_dynamic_scale_edges():
    big = jnp.float32(1e6) * jnp.ones((4,))
    q = fp8.quantize(big, 1.0, "e4m3")
    assert float(jnp.max(q.astype(jnp.float32))) <= 448.0
    assert float(fp8.dynamic_scale(jnp.zeros((4,)), 448.0)) == 1.0
    assert float(fp8.dynamic_scale(
        jnp.array([jnp.inf], jnp.float32), 448.0)) == 1.0


def test_fused_dense_module_fp8_path():
    policy = fp8.Fp8Policy()
    m = FusedDense(8, 4, param_dtype=jnp.bfloat16, fp8=policy)
    x = jax.random.normal(jax.random.key(0), (2, 8), jnp.bfloat16)
    params = m.init(jax.random.key(1), x)
    y = m.apply(params, x)
    assert y.shape == (2, 4) and y.dtype == jnp.bfloat16
    # the plain module stays the non-fp8 dot
    m0 = FusedDense(8, 4, param_dtype=jnp.bfloat16)
    y0 = m0.apply(params, x)
    jaxpr = jax.make_jaxpr(lambda p, x: m.apply(p, x))(params, x)
    from apex_tpu.lint.semantic.jaxprs import fp8_convert_counts
    assert fp8_convert_counts(jaxpr) == {"e4m3": 2}
    assert y0.shape == y.shape


def test_tensor_parallel_linear_fp8_path():
    from apex_tpu.transformer.tensor_parallel import (
        ColumnParallelLinear, RowParallelLinear)
    policy = fp8.Fp8Policy()
    x = jax.random.normal(jax.random.key(0), (2, 8), jnp.float32)
    col = ColumnParallelLinear(8, 6, fp8=policy)
    p = col.init(jax.random.key(1), x)
    y = col.apply(p, x)
    assert y.shape == (2, 6)
    row = RowParallelLinear(6, 8, fp8=policy)
    p2 = row.init(jax.random.key(2), y)
    assert row.apply(p2, y).shape == (2, 8)


def test_transformer_functional_reexports_fp8_matmul():
    from apex_tpu.transformer import functional
    assert functional.fp8_matmul is fp8_matmul


# ---------------------------------------------------------------------
# overflow: found_inf latch + held step clock + per-tensor backoff
# ---------------------------------------------------------------------

def test_overflow_latches_found_inf_and_holds_step_clock():
    policy = fp8.Fp8Policy(amax_history_len=4)
    params = _tree(key=7)
    opt = FusedAdam(params, lr=1e-2)
    opt.enable_fp8(policy)
    pipe = amp.FlatGradPipeline(optimizer=opt, fp8=policy)
    f8 = pipe.fp8_init()
    scaler = amp.LossScaleState.create(2.0 ** 4)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    # one clean step first
    flat = pipe.unscale_and_norm(pipe.pack(grads), scaler)
    flat, f8 = pipe.fp8_update(f8, flat)
    assert int(flat.found_inf) == 0
    opt.step(flat)
    assert int(opt.step_count) == 1
    clean_scale = [np.asarray(s) for s in f8.scale]
    clean_hist = [np.asarray(h) for h in f8.amax_history]
    params_before = jax.tree_util.tree_map(np.asarray, opt.params)
    # poisoned gradients: inf in one leaf
    bad = dict(grads)
    bad["w"] = grads["w"].at[0, 0].set(jnp.inf)
    flat_bad = pipe.unscale_and_norm(pipe.pack(bad), scaler)
    flat_bad, f8_bad = pipe.fp8_update(f8, flat_bad)
    assert int(flat_bad.found_inf) == 1
    opt.step(flat_bad)
    # the step clock held and params did not move
    assert int(opt.step_count) == 1
    _assert_trees_equal(opt.params, params_before)
    # fp8 history held everywhere; only the poisoned tensor's scale
    # backed off (the per-tensor backoff discipline)
    for h, hc in zip(f8_bad.amax_history, clean_hist):
        np.testing.assert_array_equal(np.asarray(h), hc)
    sc = np.concatenate([np.asarray(s) for s in f8_bad.scale])
    cl = np.concatenate(clean_scale)
    assert (sc <= cl).all() and (sc < cl).any()


def test_already_skipped_step_holds_fp8_history():
    """A loss-scale overflow (found_inf set before the fp8 update)
    must keep garbage amax out of the window entirely."""
    policy = fp8.Fp8Policy()
    tree = _tree()
    plan = cached_plan(tree)
    pipe = amp.FlatGradPipeline(plan=plan, fp8=policy)
    f8 = pipe.fp8_init()
    bufs = plan.pack_grads(tree)
    flat = pipe.unscale_and_norm(bufs, inv_scale=jnp.float32(1.0))
    flat = flat._replace(found_inf=jnp.int32(1))   # externally skipped
    flat2, f8b = pipe.fp8_update(f8, flat)
    assert int(flat2.found_inf) == 1
    _assert_trees_equal(f8b.amax_history, f8.amax_history)
    _assert_trees_equal(f8b.scale, f8.scale)


# ---------------------------------------------------------------------
# packed-state round trips
# ---------------------------------------------------------------------

def _fp8_opt(params, policy, **kw):
    opt = FusedAdam(params, lr=1e-2, **kw)
    opt.enable_fp8(policy)
    return opt


def _fp8_slots(opt):
    return {k: [np.asarray(b) for b in v]
            for k, v in opt.opt_state.items() if k.startswith("fp8_")}


def _slots_equal(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        for x, y in zip(a[k], b[k]):
            np.testing.assert_array_equal(x, y)


def test_state_dict_round_trip_bit_exact():
    policy = fp8.Fp8Policy(amax_history_len=4)
    params = _tree(key=11)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    opt = _fp8_opt(params, policy)
    opt.step(grads)
    sd = opt.state_dict()
    opt2 = _fp8_opt(params, policy)
    opt2.load_state_dict(sd)
    opt2.params = opt.params      # state_dict restores state, not params
    _slots_equal(_fp8_slots(opt), _fp8_slots(opt2))
    # continuation is bit-exact
    opt.step(grads)
    opt2.step(grads)
    _slots_equal(_fp8_slots(opt), _fp8_slots(opt2))
    _assert_trees_equal(opt.params, opt2.params)


def test_checkpoint_v2_round_trip_bit_exact(tmp_path):
    from apex_tpu import checkpoint
    policy = fp8.Fp8Policy(amax_history_len=4)
    params = _tree(key=13)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    opt = _fp8_opt(params, policy)
    opt.step(grads)
    p = str(tmp_path / "fp8.ckpt")
    checkpoint.save_training_state(p, optimizer=opt, step=1)
    with open(p, "rb") as f:
        assert b"APEX_TPU_CKPT_V2" in f.read(512)   # v2 really taken
    opt2 = _fp8_opt(params, policy)
    checkpoint.load_training_state(p, opt.params, opt2)
    _slots_equal(_fp8_slots(opt), _fp8_slots(opt2))
    opt.step(grads)
    opt2.step(grads)
    _slots_equal(_fp8_slots(opt), _fp8_slots(opt2))
    _assert_trees_equal(opt.params, opt2.params)


def test_rechunk_preserves_fp8_state_values():
    policy = fp8.Fp8Policy(amax_history_len=4)
    params = _tree(key=17)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    opt = _fp8_opt(params, policy)
    opt.step(grads)
    scales_before = jax.tree_util.tree_map(np.asarray,
                                           opt.fp8_scales())
    ref = _fp8_opt(params, policy)
    ref.step(grads)
    assert opt.rechunk(600)
    assert len(opt._plan.buckets) > 1
    scales_after = jax.tree_util.tree_map(np.asarray,
                                          opt.fp8_scales())
    _assert_trees_equal(scales_before, scales_after)
    # continuation bit-exact vs the un-rechunked twin
    opt.step(grads)
    ref.step(grads)
    _assert_trees_equal(opt.params, ref.params)
    _assert_trees_equal(opt.fp8_scales(), ref.fp8_scales())


def test_offload_round_trip_matches_resident():
    policy = fp8.Fp8Policy(amax_history_len=4)
    params = _tree(key=19)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    a = _fp8_opt(params, policy)
    b = _fp8_opt(params, policy, offload_state=True)
    for _ in range(2):
        a.step(grads)
        b.step(grads)
    _slots_equal(_fp8_slots(a), _fp8_slots(b))
    sd = b.state_dict()
    c = _fp8_opt(params, policy, offload_state=True)
    c.load_state_dict(sd)
    c.params = b.params           # state_dict restores state, not params
    c.step(grads)
    a.step(grads)
    _slots_equal(_fp8_slots(a), _fp8_slots(c))


def test_packer_vector_field_round_trip():
    tree = _tree()
    plan = cached_plan(tree)
    field = jax.tree_util.tree_map(
        lambda l: jnp.arange(6, dtype=jnp.float32)
        * (1.0 + l.size), tree)
    packed = plan.pack_state_field(field)
    assert all(b.ndim == 2 and b.shape[1] == 6 for b in packed)
    back = plan.unpack_state_field(packed)
    _assert_trees_equal(field, back)


def test_per_leaf_optimizer_rejects_enable_fp8():
    opt = FusedSGD(_tree(), lr=1e-2, fuse_buckets=False)
    with pytest.raises(ValueError, match="bucketed"):
        opt.enable_fp8(fp8.Fp8Policy())


# ---------------------------------------------------------------------
# dispatch prefs: tuned policy + int8 routing
# ---------------------------------------------------------------------

def test_tuned_policy_reads_prefs(monkeypatch):
    from apex_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "_FP8",
                        {"amax_history_len": 8, "interval": 4})
    p = fp8.tuned_policy()
    assert p.amax_history_len == 8 and p.interval == 4
    assert fp8.tuned_policy(interval=2).interval == 2   # override wins


def test_int8_matmul_auto_routes_through_prefs(monkeypatch):
    from apex_tpu.ops import _dispatch
    from apex_tpu.quantization import int8_matmul, quantize_int8
    x = jax.random.normal(jax.random.key(0), (4, 8), jnp.bfloat16)
    w = quantize_int8(jax.random.normal(jax.random.key(1),
                                        (8, 4)) * 0.1)
    monkeypatch.setattr(_dispatch, "_QUANT", {"int8_dynamic": True})
    auto = int8_matmul(x, w, dynamic=None)
    dyn = int8_matmul(x, w, dynamic=True)
    wo = int8_matmul(x, w, dynamic=False)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(dyn))
    monkeypatch.setattr(_dispatch, "_QUANT", {})
    auto2 = int8_matmul(x, w, dynamic=None)
    np.testing.assert_array_equal(np.asarray(auto2), np.asarray(wo))
    # an explicit bool always beats the table
    monkeypatch.setattr(_dispatch, "_QUANT", {"int8_dynamic": True})
    np.testing.assert_array_equal(
        np.asarray(int8_matmul(x, w, dynamic=False)), np.asarray(wo))


def test_prefs_table_normalizes_fp8_and_quant_sections():
    from apex_tpu.ops._dispatch import _normalize_doc
    t = _normalize_doc({
        "fp8": {"amax_history_len": 8, "interval": "bogus"},
        "quantization": {"int8_dynamic": True, "junk": 1}}, None)
    assert t.fp8 == {"amax_history_len": 8}
    assert t.quantization == {"int8_dynamic": True}


# ---------------------------------------------------------------------
# watchdog: fp8 scale collapse -> rollback -> bit-exact replay
# ---------------------------------------------------------------------

def test_fp8_detector_fires_on_pinned_scale_only():
    from apex_tpu.resilience.watchdog import Fp8ScaleCollapseDetector
    det = Fp8ScaleCollapseDetector(floor=1.0, windows=2)
    healthy = [{"step": s, "fp8/scale_min": 64.0} for s in range(4)]
    assert det.observe(healthy) == []
    pinned = [{"step": s, "fp8/scale_min": 0.5} for s in range(4, 8)]
    assert det.observe(pinned) == []            # first floored window
    a = det.observe([{"step": s, "fp8/scale_min": 0.25}
                     for s in range(8, 12)])
    assert len(a) == 1 and a[0].kind == "fp8_scale_collapse"
    assert a[0].severity == "critical"
    # no-information windows don't count either way
    det.reset()
    assert det.observe([{"step": 0, "loss": 1.0}]) == []


def test_default_fp8_detector_ignores_no_signal_init_scale():
    """A tensor with no gradient signal keeps its INIT scale of
    exactly 1.0 forever — the default-suite detector must read that
    as healthy, not as a collapse (its default floor is 2^-8)."""
    from apex_tpu.resilience.watchdog import Fp8ScaleCollapseDetector
    det = Fp8ScaleCollapseDetector()
    for w in range(4):
        assert det.observe(
            [{"step": w * 4 + s, "fp8/scale_min": 1.0}
             for s in range(4)]) == []
    # eight consecutive backoffs from init IS a storm
    det2 = Fp8ScaleCollapseDetector()
    det2.observe([{"step": 0, "fp8/scale_min": 2.0 ** -8}])
    a = det2.observe([{"step": 1, "fp8/scale_min": 2.0 ** -9}])
    assert len(a) == 1 and a[0].kind == "fp8_scale_collapse"


def test_fp8_collapse_in_default_suite_and_actions():
    from apex_tpu.resilience.watchdog import (DEFAULT_ACTIONS,
                                              default_detectors)
    assert DEFAULT_ACTIONS["fp8_scale_collapse"] == "rollback"
    kinds = [getattr(d, "kind", None) for d in default_detectors()]
    assert "fp8_scale_collapse" in kinds


class _Fp8Job:
    """Self-healing fp8 run: eager loop recording fp8/scale_min into
    the telemetry ring; a pinned-scale storm must roll back to LKG
    and replay bit-exactly (the metric stream was poisoned, the
    optimizer path is deterministic — and the fp8 slots ride the v2
    checkpoint through the rollback)."""

    TOTAL, EVERY = 24, 3

    def __init__(self, ckpt_dir, storm_steps=0):
        from apex_tpu import telemetry as telemetry_mod
        from apex_tpu.resilience import CheckpointManager
        from apex_tpu.resilience.retry import RetryPolicy
        from apex_tpu.resilience.watchdog import (
            Fp8ScaleCollapseDetector, Watchdog, WatchdogPolicy)
        params = _tree(key=23)
        self.opt = _fp8_opt(params, fp8.Fp8Policy(amax_history_len=4))
        self.g = jax.tree_util.tree_map(
            lambda p: jnp.ones_like(p) * 1e-2, params)
        self.mgr = CheckpointManager(ckpt_dir, keep=3, every=self.EVERY)
        self.template = jax.tree_util.tree_map(jnp.zeros_like, params)
        self.tel = telemetry_mod.Telemetry(run_dir=None, window=4,
                                           retrace=False)
        self.wd = Watchdog(
            detectors=[Fp8ScaleCollapseDetector(floor=1.0, windows=2)],
            policy=WatchdogPolicy(rollback=RetryPolicy(
                max_retries=2, base_delay_s=0.0)),
            telemetry=self.tel, clean_window=4)
        self.storm_budget = storm_steps

    def step_fn(self, step):
        self.opt.step(self.g)
        scale_min = 64.0
        if step >= 8 and self.storm_budget > 0:
            self.storm_budget -= 1          # APPLICATION-budgeted:
            scale_min = 0.5                 # replays land clean
        self.tel.record({"fp8/scale_min": scale_min}, step)

    def run(self):
        from apex_tpu.resilience import run_elastic
        return run_elastic(self.step_fn, self.mgr, self.opt,
                           total_steps=self.TOTAL,
                           params_like=self.template,
                           watchdog=self.wd, backoff_s=0.0)

    def close(self):
        self.wd.close()
        self.tel.close()
        self.mgr.close()


def test_fp8_scale_collapse_rolls_back_and_replays_bit_exact(tmp_path):
    ref = _Fp8Job(str(tmp_path / "ref"))
    res = ref.run()
    assert res.step == _Fp8Job.TOTAL and res.rollbacks == 0
    ref.close()

    job = _Fp8Job(str(tmp_path / "storm"), storm_steps=8)
    with pytest.warns(UserWarning, match="watchdog rollback"):
        res = job.run()
    assert res.step == _Fp8Job.TOTAL and res.rollbacks == 1
    assert "fp8_scale_collapse" in [a.kind for a in job.wd.timeline]
    rb = [e for e in job.wd.events if e["action"] == "rollback"]
    assert rb and rb[0]["to_step"] is not None
    # bit-exact replay, fp8 slots included
    _assert_trees_equal(job.opt.params, ref.opt.params)
    _slots_equal(_fp8_slots(job.opt), _fp8_slots(ref.opt))
    job.close()


# ---------------------------------------------------------------------
# the spec + bench smoke
# ---------------------------------------------------------------------

def test_fp8_step_spec_passes():
    from apex_tpu.lint.semantic.registry import verify_all
    (res,) = verify_all(["amp.fp8_step"])
    assert res.ok, res.failures
    assert "fp8_quantize_counts" in res.checked
    assert "donated_aliases_min" in res.checked
    assert "no_host_transfer" in res.checked


def test_fp8_bench_smoke():
    from apex_tpu.amp.fp8_bench import (bench_fp8_matmul,
                                        bench_fp8_scale_update)
    r = bench_fp8_matmul(m=32, k=32, n=32, iters=2, reps=2)
    assert r["fp8_matmul_ms"] > 0 and r["bf16_matmul_ms"] > 0
    assert r["fp8_matmul_speedup"] is not None
    r2 = bench_fp8_scale_update(layers=3, hidden=16, iters=2, reps=2)
    assert r2["fp8_scale_fused_ms"] > 0
    assert r2["fp8_scale_update_speedup"] is not None


def test_budget_has_fp8_row():
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "tools", "perf_budget.json")) as f:
        budget = json.load(f)
    row = budget["metrics"]["extra.fp8_matmul_speedup"]
    assert row["floor"] == 1.5 and row["direction"] == "higher"
