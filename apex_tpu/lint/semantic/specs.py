"""Built-in invariant specs: the library's public jitted entry points.

Every spec here traces a REAL public entry point with tiny abstract
inputs and pins the structural facts earlier PRs proved ad hoc:

* the five fused optimizers, per-leaf AND bucketed — zero host
  transfer primitives, no kernel in the update (the bucketed step is
  ``jnp`` that XLA fuses with the overflow skip and the model-dtype
  copy, PERF.md section 6, PR 28), the single bucket-sized gradient
  pack, donation reflected as input-output aliasing in the lowered
  HLO, no f64;
* the flat AMP pipeline step — 1 Pallas call per bucket (unscale+norm
  fused, ahead of the optimizer's XLA sweeps), never a per-leaf finite
  check;
* ``amp.scaled_value_and_grad`` (per-leaf oracle surface) — no host
  traffic, no f64;
* the interleaved-schedule DDP step (chunked buckets + the
  reduce-in-backward seam) — one psum per bucket whose dependency
  cone is a proper, distinct subset of the backward's compute
  (collectives schedulable under remaining compute, never all
  trailing), donation aliasing intact;
* the fused microbatch-accumulation step — one pack + one
  ``flat_accumulate`` per bucket, accumulator buffers aliased in the
  lowered HLO (the add is in place), zero per-leaf work;
* a telemetry-instrumented step — ZERO callback/transfer primitives
  (the ring write is a plain dynamic_update_slice) — and the same
  step with a resilience Watchdog attached (detectors are host-side,
  window-cadence only: self-healing adds no per-step syncs);
* ``all_reduce_flat_buffers`` under shard_map — exactly one psum per
  bucket, every collective bound to the declared axis, none dead;
* the serving engine's AOT programs — the decode window free of
  host traffic with the arena + slot-state donation pinned as exact
  lowered-HLO alias counts, and the per-bucket prefill running one
  flash ``pallas_call`` per decoder layer into the donated arena.

Expected Pallas counts adapt to the dispatch gate
(``ops._dispatch.op_enabled``): when the multi_tensor family is
routed to the XLA reference path (env override, measured prefs) the
kernel-count invariant is dropped rather than asserting a count the
dispatcher made false — the transfer/donation/dtype invariants hold
on either path.

Tiny shapes keep the whole pass cheap (tools/check.sh budgets the
full AST+semantic run at < 60 s on one CPU core).
"""

from __future__ import annotations

import functools

from apex_tpu.lint.semantic.registry import register_spec

_BUCKETED_OPTIMIZERS = ("FusedAdam", "FusedSGD", "FusedAdagrad",
                        "FusedNovoGrad", "FusedLAMB")

# the segmented optimizers broadcast each tensor's scalar back over its
# static extent (ops.multi_tensor.flat_segment_broadcast): a second
# bucket-sized floating concatenate per bucket beside the gradient pack
_SEGMENTED = {"FusedNovoGrad", "FusedLAMB"}


def _tiny_params():
    import jax.numpy as jnp
    return {"a": jnp.ones((8, 8), jnp.float32),
            "b": jnp.zeros((8,), jnp.float32),
            "c": jnp.ones((4, 4), jnp.float32) * 0.5}


def _mlp_params(layers=3):
    import jax.numpy as jnp
    return {f"l{i}": {"w": jnp.ones((8, 8), jnp.float32) * 0.1,
                      "b": jnp.zeros((8,), jnp.float32)}
            for i in range(layers)}


def _mlp_loss(p, x):
    import jax.numpy as jnp
    h = x
    for k in sorted(p):
        h = jnp.tanh(h @ p[k]["w"] + p[k]["b"])
    return jnp.mean(h ** 2)


def _traced_hypers(opt):
    import jax.numpy as jnp
    return {k: jnp.asarray(v, jnp.float32)
            for k, v in opt.hypers.items()
            if isinstance(v, float) and not isinstance(v, bool)}


def _optimizer(name, masters=False, **kw):
    """``masters``: bfloat16 parameters, so the optimizer keeps float32
    masters and the step writes the model-dtype copy as well."""
    import jax
    import jax.numpy as jnp
    from apex_tpu import optimizers
    params = _tiny_params()
    if masters:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
    return getattr(optimizers, name)(params, lr=1e-3, **kw)


def _step_args(opt):
    """The step program's arguments: the packed operands as ``step()``
    gives them (the optimizer's own ``_step_args``), all-ones
    gradients, step 1 and the float hypers."""
    import jax
    import jax.numpy as jnp
    grads = jax.tree_util.tree_map(jnp.ones_like, opt.params)
    params, masters, state = opt._step_args(grads)[:3]
    return (params, masters, state, grads, jnp.int32(1),
            jnp.float32(1.0), _traced_hypers(opt), jnp.int32(0))


def _build_bucketed(name, **kw):
    import jax
    opt = _optimizer(name, **kw)
    assert opt._plan is not None, f"{name}: packer declined tiny tree"
    args = _step_args(opt)
    nb = len(opt._plan.buckets)
    # the step updates in place what the optimizer holds packed
    # (FusedLAMB keeps its masters out: fused_lamb.py says why)
    n_donated = len(jax.tree_util.tree_leaves(
        [args[i] for i in opt._donation["donate_argnums"]]))
    expect = {
        "no_host_transfer": True,
        "no_f64": True,
        # ONE gradient pack: a bucket-sized concatenate per bucket
        "bucket_concats": {"count": nb * (2 if name in _SEGMENTED else 1),
                           "sizes": {(b.size,)
                                     for b in opt._plan.buckets}},
        # donation honored: every donated packed buffer aliases an
        # output
        "donated_aliases": n_donated,
        "no_orphan_collectives": True,
        # the update is jnp: XLA fuses it with the skip and the cast
        "pallas_calls": 0,
        "is_finite_max": 0,           # found_inf arrives as a flag
    }
    return {"fn": opt._full_step_impl, "args": args,
            "jit_kwargs": opt._donation, "expect": expect}


def _build_per_leaf(name, **kw):
    import jax
    opt = _optimizer(name, fuse_buckets=False, **kw)
    assert opt._plan is None
    args = _step_args(opt)
    n_state = len(jax.tree_util.tree_leaves(opt.opt_state))
    return {
        "fn": opt._full_step_impl, "args": args,
        "jit_kwargs": opt._donation,
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "pallas_calls": 0,        # the per-leaf oracle is pure XLA
            "donated_aliases": n_state,
            "no_orphan_collectives": True,
        },
    }


_OPT_KW = {"FusedSGD": {"momentum": 0.9}}

for _name in sorted(_BUCKETED_OPTIMIZERS):
    _anchor = ("apex_tpu/optimizers/"
               f"{_name.replace('Fused', 'fused_').lower()}.py")
    register_spec(
        f"optim.{_name}.bucketed", anchor=_anchor,
        description=f"bucketed {_name} step: fused XLA sweeps per "
                    "bucket, one grad pack, donated state, zero host "
                    "traffic")(
        functools.partial(_build_bucketed, _name,
                          **_OPT_KW.get(_name, {})))
    register_spec(
        f"optim.{_name}.per_leaf", anchor=_anchor,
        description=f"per-leaf {_name} oracle step: pure XLA, donated "
                    "state, zero host traffic")(
        functools.partial(_build_per_leaf, _name,
                          **_OPT_KW.get(_name, {})))


@register_spec(
    "amp.flat_pipeline_step",
    anchor="apex_tpu/amp/flat_pipeline.py",
    description="flat AMP train step: one grad pack per bucket, "
                "unscale+norm fused (1 pallas/bucket; FusedAdam's update "
                "is XLA), "
                "no per-leaf finite checks, zero host traffic")
def _build_flat_pipeline_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers._base import _fold_clip
    from apex_tpu.ops._dispatch import op_enabled

    params = _mlp_params()
    x = jax.random.normal(jax.random.key(0), (4, 8))
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    plan = opt._plan
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0)
    hypers = _traced_hypers(opt)
    nb = len(plan.buckets)

    def flat_step(param_bufs, opt_state, scaler, x, step):
        ptree = plan.unpack_model(param_bufs)
        loss, flat = pipe.scaled_value_and_grad(_mlp_loss, scaler,
                                                ptree, x)
        new_bufs, _, new_state = opt._full_step_flat(
            param_bufs, None, opt_state, flat.bufs, step,
            _fold_clip(1.0, flat.clip_coef), hypers, flat.found_inf)
        return loss, new_bufs, new_state

    args = (opt._param_bufs, opt.opt_state, scaler, x, jnp.int32(1))
    expect = {
        "no_host_transfer": True,
        "no_f64": True,
        "bucket_concats": {"count": nb,
                           "sizes": {(b.size,) for b in plan.buckets}},
        # per-BUCKET finite checks at most — never per leaf (even the
        # XLA fallback oracle is once per bucket)
        "is_finite_max": nb,
        "no_orphan_collectives": True,
    }
    if op_enabled("multi_tensor"):
        # exactly unscale_norm per bucket: clipping folds into the
        # optimizer update's grad scaling (XLA sweeps, no kernel),
        # nothing else touches the gradients
        expect["pallas_calls"] = nb
        expect["is_finite_max"] = 0
    return {"fn": flat_step, "args": args, "expect": expect}


@register_spec(
    "amp.interleaved_flat_step",
    anchor="apex_tpu/amp/flat_pipeline.py",
    description="interleaved-schedule flat AMP DDP step (chunked "
                "buckets + reduce-in-backward seam): one psum per "
                "bucket whose dependency cone is a proper, distinct "
                "subset of the backward's compute — the collectives "
                "are schedulable under remaining compute, NOT "
                "trailing; donation aliasing intact, zero host "
                "traffic")
def _build_interleaved_flat_step():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu import amp, comm
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers._base import _fold_clip

    params = _mlp_params()
    # ~300 B cap: one 8x8+8 f32 layer (288 B) per bucket -> 3 buckets,
    # 3 per-bucket collectives with distinct cotangent cones
    opt = FusedAdam(params, lr=1e-3, max_bucket_bytes=300)
    plan = opt._plan
    nb = len(plan.buckets)
    assert nb >= 2, "chunking produced a monolithic plan"
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0,
                                axis_name=comm.AXIS_DATA,
                                interleave=True)
    hypers = _traced_hypers(opt)
    scaler = amp.LossScaleState.create()
    x = jax.random.normal(jax.random.key(3), (8, 8))
    mesh = Mesh(np.array(jax.devices()[:1]), (comm.AXIS_DATA,))

    def flat_step(param_bufs, opt_state, scaler, x, step):
        ptree = plan.unpack_model(param_bufs)
        loss, flat = pipe.scaled_value_and_grad(_mlp_loss, scaler,
                                                ptree, x)
        new_bufs, _, new_state = opt._full_step_flat(
            param_bufs, None, opt_state, flat.bufs, step,
            _fold_clip(1.0, flat.clip_coef), hypers, flat.found_inf)
        return loss, new_bufs, new_state

    fn = comm.shard_map(
        flat_step, mesh,
        in_specs=(P(), P(), P(), P(comm.AXIS_DATA), P()),
        out_specs=P())
    args = (opt._param_bufs, opt.opt_state, scaler, x, jnp.int32(1))
    n_state = len(jax.tree_util.tree_leaves(opt.opt_state))
    return {
        "fn": fn, "args": args,
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "psum_count": nb,
            "collective_axes": {comm.AXIS_DATA},
            "interleaved_collectives": {"min_collectives": 2},
            "donated_aliases_min": n_state,
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "amp.flat_accumulate_step",
    anchor="apex_tpu/amp/flat_pipeline.py",
    description="fused microbatch accumulation step: one gradient "
                "pack + one flat_accumulate read-modify-write per "
                "bucket, accumulator buffers DONATED (aliased in the "
                "lowered HLO — the add is in place), found_inf "
                "latched on device, zero per-leaf work, zero host "
                "traffic")
def _build_flat_accumulate_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.ops._dispatch import op_enabled

    params = _tiny_params()
    opt = FusedAdam(params, lr=1e-3)
    plan = opt._plan
    nb = len(plan.buckets)
    pipe = amp.FlatGradPipeline(optimizer=opt)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    acc0 = opt.grad_accum_init()

    def accum_step(acc, grads):
        return pipe.accumulate(acc, grads)

    expect = {
        "no_host_transfer": True,
        "no_f64": True,
        # ONE pack per bucket feeding the fused add — and nothing else
        "bucket_concats": {"count": nb,
                           "sizes": {(b.size,) for b in plan.buckets}},
        # the accumulator buckets alias outputs: the add is in place
        "donated_aliases_min": nb,
        "no_orphan_collectives": True,
    }
    if op_enabled("multi_tensor"):
        expect["pallas_calls"] = nb        # flat_accumulate per bucket
        expect["is_finite_max"] = 0
    return {
        "fn": accum_step, "args": (acc0, grads),
        "jit_kwargs": {"donate_argnums": (0,)},
        "expect": expect,
    }


@register_spec(
    "amp.fp8_step",
    anchor="apex_tpu/amp/fp8.py",
    description="fp8 delayed-scaling flat AMP train step: EXACT "
                "quantize-convert counts (2 e4m3 per matmul forward, "
                "ONE shared e5m2 cotangent per matmul backward — "
                "precision casts cannot silently multiply), packed "
                "fp8 scale state donated/aliased like every other "
                "optimizer slot, zero host traffic, no f64")
def _build_fp8_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.amp import fp8 as fp8_mod
    from apex_tpu.fused_dense import fp8_matmul
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers._base import _fold_clip

    policy = fp8_mod.Fp8Policy(amax_history_len=4)
    params = _mlp_params()           # 3 layers -> 3 fp8 matmuls
    n_matmuls = len(params)
    x = jax.random.normal(jax.random.key(4), (4, 8))
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    opt.enable_fp8(policy)
    plan = opt._plan
    nb = len(plan.buckets)
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0,
                                fp8=policy)
    hypers = _traced_hypers(opt)
    f8 = pipe.fp8_init()

    def fp8_loss(p, scales, x):
        h = x
        for k in sorted(p):
            h = jnp.tanh(fp8_matmul(h, p[k]["w"], policy=policy,
                                    w_scale=scales[k]["w"])
                         + p[k]["b"])
        return jnp.mean(h ** 2)

    def fp8_step(param_bufs, opt_state, f8, scaler, x, step):
        ptree = plan.unpack_model(param_bufs)
        scales = opt.fp8_scales(opt_state)   # packed-slot slices
        loss, flat, new_f8 = pipe.scaled_value_and_grad(
            fp8_loss, scaler, ptree, scales, x, fp8_state=f8)
        new_bufs, _, new_state = opt._full_step_flat(
            param_bufs, None, opt_state, flat.bufs, step,
            _fold_clip(1.0, flat.clip_coef), hypers, flat.found_inf)
        return loss, new_bufs, new_state, new_f8

    args = (opt._param_bufs, opt.opt_state, f8, scaler, x,
            jnp.int32(1))
    import jax as _jax
    n_state = len(_jax.tree_util.tree_leaves(opt.opt_state))
    return {
        "fn": fp8_step, "args": args,
        "jit_kwargs": {"donate_argnums": (1, 2)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            # the exact quantize economy: 2 e4m3 per matmul forward
            # (x and w), ONE e5m2 per matmul backward (the cotangent,
            # shared by dx and dw)
            "fp8_quantize_counts": {"e4m3": 2 * n_matmuls,
                                    "e5m2": n_matmuls},
            # every packed slot — the fp8 amax history and scales
            # included — aliases an output in the lowered HLO
            "donated_aliases_min": n_state,
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "amp.scaled_value_and_grad",
    anchor="apex_tpu/amp/scaler.py",
    description="per-leaf amp oracle surface: scaled loss, unscaled "
                "grads, on-device overflow flag, zero host traffic")
def _build_scaled_value_and_grad():
    import jax
    from apex_tpu import amp

    params = _mlp_params(layers=2)
    x = jax.random.normal(jax.random.key(1), (4, 8))
    scaler = amp.LossScaleState.create()

    def fn(params, scaler, x):
        return amp.scaled_value_and_grad(_mlp_loss, scaler, params, x)

    return {
        "fn": fn, "args": (params, scaler, x),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "no_orphan_collectives": True,
        },
    }


def _instrumented_step_jaxpr(with_watchdog: bool = False,
                             with_fleet: bool = False,
                             with_controller: bool = False,
                             with_exporter: bool = False):
    """The telemetry-instrumented flat-AMP step's jaxpr, optionally
    with a resilience watchdog, a fleet monitor, a fleet autoscale
    controller and/or a live MetricsServer attached to the session —
    all are host-side (window-cadence detectors; out-of-band beacons;
    window-flush decision policy; flush-time scrape republish), so the
    traced program must be byte-for-byte free of callbacks/transfers
    either way."""
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp, telemetry
    from apex_tpu.optimizers import FusedAdam

    params = _mlp_params()
    x = jax.random.normal(jax.random.key(2), (4, 8))
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0)
    tel = telemetry.Telemetry(run_dir=None, window=8, retrace=False)
    wd = None
    mon = None
    ctrl = None
    srv = None
    try:
        if with_watchdog:
            from apex_tpu.resilience.watchdog import Watchdog
            wd = Watchdog(telemetry=tel)
        if with_fleet or with_controller:
            from apex_tpu.resilience import fleet as fleet_mod
            mon = fleet_mod.FleetMonitor(
                channel=fleet_mod.LocalChannel(), host=0, n_hosts=2,
                slow_after_steps=4, dead_after_steps=8,
                slow_after_s=None, dead_after_s=None, telemetry=tel)
            mon.beat(0)           # beacons are published host-side
        if with_controller:
            from apex_tpu.resilience import fleet as fleet_mod
            ctrl = fleet_mod.FleetController(
                telemetry=tel, step_time_high_s=60.0)
            ctrl.note_step(0, 0.1)        # host-side intake
            ctrl.decide(0, n_hosts=2)     # host-side decision
        if with_exporter:
            from apex_tpu.telemetry.export import MetricsServer
            srv = MetricsServer(telemetry=tel, port=0)
            tel.flush()                   # republish path exercised

        def train_step(work_bufs, opt_state, scaler, x, step):
            ptree = opt._plan.unpack_model(work_bufs)
            loss, flat = pipe.scaled_value_and_grad(_mlp_loss, scaler,
                                                    ptree, x)
            new_bufs, _, new_state = opt._full_step_flat(
                work_bufs, None, opt_state, flat.bufs, step, 1.0,
                {}, flat.found_inf)
            return loss, new_bufs, new_state

        wrapped = tel.instrument(train_step)
        jaxpr = jax.make_jaxpr(wrapped)(
            tel.buf, jnp.int32(0), opt._param_bufs, opt.opt_state,
            scaler, x, jnp.int32(1))
    finally:
        if srv is not None:
            srv.close()
        if ctrl is not None:
            ctrl.close()
        if mon is not None:
            mon.close()
        if wd is not None:
            wd.close()
        tel.close()
    return jaxpr


@register_spec(
    "telemetry.instrumented_step",
    anchor="apex_tpu/telemetry/session.py",
    description="telemetry-instrumented flat AMP step: ZERO "
                "callback/transfer primitives; the ring write is a "
                "plain dynamic_update_slice riding the step's jit")
def _build_instrumented_step():
    return {
        "jaxpr": _instrumented_step_jaxpr(with_watchdog=False),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "dus_min": 1,             # the whole-row ring write
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "watchdog.instrumented_step",
    anchor="apex_tpu/resilience/watchdog.py",
    description="watchdog-attached instrumented flat AMP step: the "
                "anomaly detectors are host-side and window-cadence "
                "only, so the traced step still contains ZERO "
                "callback/transfer primitives — self-healing adds no "
                "per-step device syncs")
def _build_watchdog_instrumented_step():
    return {
        "jaxpr": _instrumented_step_jaxpr(with_watchdog=True),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "dus_min": 1,             # the ring write, nothing more
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "fleet.instrumented_step",
    anchor="apex_tpu/resilience/fleet.py",
    description="fleet-monitored instrumented flat AMP step: the "
                "liveness beacon is published host-side through an "
                "out-of-band channel at step boundaries, so the "
                "traced step still contains ZERO callback/transfer "
                "primitives — peer-failure detection adds no "
                "per-step device syncs")
def _build_fleet_instrumented_step():
    return {
        "jaxpr": _instrumented_step_jaxpr(with_fleet=True),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "dus_min": 1,             # the ring write, nothing more
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "fleet.autoscaled_step",
    anchor="apex_tpu/resilience/fleet.py",
    description="controller-observed instrumented flat AMP step: the "
                "fleet autoscaler is a host-side window-flush "
                "observer emitting typed grow/shrink/stay decisions, "
                "so the traced step still contains ZERO "
                "callback/transfer primitives — load-driven scaling "
                "adds no per-step device syncs")
def _build_fleet_autoscaled_step():
    return {
        "jaxpr": _instrumented_step_jaxpr(with_fleet=True,
                                          with_controller=True),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "dus_min": 1,             # the ring write, nothing more
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "telemetry.exported_step",
    anchor="apex_tpu/telemetry/export.py",
    description="live-exported instrumented flat AMP step: the "
                "MetricsServer republishes FLUSHED host data only "
                "(observer + hostmetrics sink + emitter fan-out), so "
                "the traced step still contains ZERO "
                "callback/transfer primitives — a /metrics scrape "
                "surface adds no per-step device syncs")
def _build_exported_step():
    return {
        "jaxpr": _instrumented_step_jaxpr(with_exporter=True),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "dus_min": 1,             # the ring write, nothing more
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "profiler.annotated_step",
    anchor="apex_tpu/telemetry/profiler/capture.py",
    description="profiler-capable (annotate_step-wrapped) flat AMP "
                "step: capture-off instrumentation is a trace-time "
                "named scope that lowers to NOTHING — zero "
                "callback/transfer primitives, no f64, no dead "
                "collectives")
def _build_profiler_annotated_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.telemetry.profiler import annotate_step

    params = _mlp_params()
    x = jax.random.normal(jax.random.key(2), (4, 8))
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0)

    def train_step(work_bufs, opt_state, scaler, x, step):
        ptree = opt._plan.unpack_model(work_bufs)
        loss, flat = pipe.scaled_value_and_grad(_mlp_loss, scaler,
                                                ptree, x)
        new_bufs, _, new_state = opt._full_step_flat(
            work_bufs, None, opt_state, flat.bufs, step, 1.0,
            {}, flat.found_inf)
        return loss, new_bufs, new_state

    return {
        "fn": annotate_step(train_step, name="profiled_step"),
        "args": (opt._param_bufs, opt.opt_state, scaler, x,
                 jnp.int32(1)),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "no_orphan_collectives": True,
        },
    }


def _serving_fixture(kv_dtype="f32"):
    """Tiny serving geometry shared by the serving specs."""
    import jax
    from apex_tpu import serving
    cfg = serving.DecoderConfig(vocab_size=32, hidden=8, n_layers=2,
                                n_heads=2, n_kv_heads=2, ffn=16,
                                max_seq=16, eos_token=1)
    params = serving.init_params(jax.random.key(3), cfg)
    spec = serving.ArenaSpec(n_layers=cfg.n_layers,
                             n_kv_heads=cfg.n_kv_heads,
                             head_dim=cfg.head_dim, page_size=4,
                             n_pages=8, max_slots=2, pages_per_slot=4)
    return cfg, params, spec, serving.KVArena(spec, dtype=kv_dtype)


@register_spec(
    "serving.decode_step",
    anchor="apex_tpu/serving/steps.py",
    description="AOT decode window: a continuously-batched greedy "
                "decode step over the paged KV arena lowers with ZERO "
                "transfer/callback primitives (admission/eviction "
                "state rides device-side slots, read once per flush "
                "window) and the arena + slot-state donation is "
                "pinned as tf.aliasing_output in the lowered HLO — "
                "exactly every carry buffer the step UPDATES (the "
                "pass-through leaves — page_table, active, the float-"
                "mode scale stubs and the host-written sampling "
                "params — alias nothing)")
def _build_serving_decode_step():
    import jax
    from apex_tpu import serving
    cfg, params, spec, arena = _serving_fixture()
    state = serving.init_state(arena, window=2)
    fn = serving.decode_window_fn(cfg, spec, window=2)
    # k, v, seq_lens, last_token, budget, out_tokens, n_out, done
    # update in the window; the scale stubs and sampling params pass
    # through but XLA still trivially aliases their donated buffers —
    # only page_table and active (gather-feeding reads) end up
    # unaliased in the lowered HLO, the same two as at seed
    updated = len(jax.tree_util.tree_leaves(state)) - 2
    return {
        "fn": fn, "args": (params, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "donated_aliases": updated,
            "no_orphan_collectives": True,
        },
        # apexcost: grade serving HBM per decode slot from the donated
        # carry (arena pages + scale planes + slot state), and pin the
        # arena geometry for the peak-fits-arena cross-check
        "cost_meta": {
            "serving_slots": spec.max_slots,
            "arena_bytes": int(arena.k.nbytes + arena.v.nbytes
                               + arena.k_scale.nbytes
                               + arena.v_scale.nbytes),
        },
    }


@register_spec(
    "serving.decode_step_quantized",
    anchor="apex_tpu/serving/steps.py",
    description="AOT decode window over the INT8 arena: still zero "
                "host traffic, the scale planes now update alongside "
                "the pages (two more donated aliases than the float "
                "window), and the cast economy is pinned EXACTLY — "
                "one dequantize-in-gather and one quantize-on-scatter "
                "convert per arena side per step, never per layer or "
                "per consumer")
def _build_serving_decode_step_quantized():
    import jax
    from apex_tpu import serving
    cfg, params, spec, arena = _serving_fixture(kv_dtype="int8")
    state = serving.init_state(arena, window=2)
    fn = serving.decode_window_fn(cfg, spec, window=2)
    # same alias set as the float window (leaves - 2: page_table and
    # active stay unaliased) — but here k_scale/v_scale alias because
    # the scatter genuinely UPDATES them, not by trivial pass-through
    updated = len(jax.tree_util.tree_leaves(state)) - 2
    return {
        "fn": fn, "args": (params, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "donated_aliases": updated,
            "int8_convert_counts": {"to_int8": 2, "from_int8": 2},
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "serving.sample_step",
    anchor="apex_tpu/serving/steps.py",
    description="device-side sampling: the temperature/top-k/top-p "
                "categorical draw traces to pure device compute — "
                "zero transfer/callback primitives (the PRNG key "
                "rides the donated carry, draws fold in the absolute "
                "position) and exactly ONE shared descending sort "
                "feeds both nucleus filters")
def _build_serving_sample_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import serving
    b, v = 2, 32
    args = (jnp.zeros((b, v), jnp.float32),
            jnp.zeros((b, 2), jnp.uint32),
            jnp.zeros((b,), jnp.int32),
            jnp.full((b,), 0.7, jnp.float32),
            jnp.full((b,), 5, jnp.int32),
            jnp.full((b,), 0.9, jnp.float32))
    return {
        "fn": serving.sample_tokens, "args": args,
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "counter": {"sort": 1},
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "serving.prefill_step",
    anchor="apex_tpu/serving/steps.py",
    description="AOT per-bucket prefill: one flash-attention "
                "pallas_call per decoder layer over the padded "
                "prompt, K/V pages scattered into the DONATED arena "
                "(both arena buffers aliased in the lowered HLO), "
                "zero host traffic")
def _build_serving_prefill_step():
    import jax
    import jax.numpy as jnp
    from apex_tpu import serving
    from apex_tpu.ops._dispatch import op_enabled
    cfg, params, spec, arena = _serving_fixture()
    bucket = 8
    fn = serving.prefill_fn(cfg, spec, bucket)
    args = (params, arena.k, arena.v, arena.k_scale, arena.v_scale,
            jnp.zeros((bucket // spec.page_size,), jnp.int32),
            jnp.zeros((bucket,), jnp.int32), jnp.int32(5),
            jnp.zeros((2,), jnp.uint32), jnp.float32(0.0),
            jnp.int32(0), jnp.float32(1.0))
    expect = {
        "no_host_transfer": True,
        "no_f64": True,
        # the K and V arenas plus both scale planes (pass-through
        # stubs in float mode, but still trivially aliased)
        "donated_aliases": 4,
        "no_orphan_collectives": True,
    }
    if op_enabled("attention_f32"):   # dispatch-gate aware, like optim
        expect["pallas_calls"] = cfg.n_layers
    return {"fn": fn, "args": args,
            "jit_kwargs": {"donate_argnums": (1, 2, 3, 4)},
            "expect": expect}


@register_spec(
    "serving.spec_decode_step",
    anchor="apex_tpu/serving/steps.py",
    description="speculative decode window (self-drafting, K=2): the "
                "n-gram drafter, dense K+1-position verify forward and "
                "branch-free accept/rollback all lower to pure device "
                "compute with ZERO transfer/callback primitives — the "
                "one-device_get-per-window contract survives "
                "speculation — and exactly ONE shared sort feeds the "
                "whole verify pass's sampling (all K+1 positions drawn "
                "in one batched sample_tokens call, keys folded per "
                "absolute position)")
def _build_serving_spec_decode_step():
    import jax
    from apex_tpu import serving
    cfg, params, spec, arena = _serving_fixture()
    state = serving.init_state(arena, window=2, spec_k=2)
    fn = serving.decode_window_fn(cfg, spec, window=2, spec_k=2)
    return {
        "fn": fn, "args": (params, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            # measured: 15 of the 19 donated carry leaves alias —
            # two fewer than the K=0 window's 17 (leaves - 2), the
            # speculative counters reset from fresh zeros each window
            "donated_aliases": 15,
            "counter": {"sort": 1},
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "serving.decode_step_w8",
    anchor="apex_tpu/serving/model.py",
    description="AOT decode window over INT8 serving weights: the six "
                "decoder matmul planes (wq/wk/wv/wo/w1/w2) dequantize "
                "exactly once per use site — 6 x n_layers from_int8 "
                "converts, ZERO to_int8 (weights quantize at engine "
                "build, never in the step) — with zero host traffic "
                "and the same donated-carry alias set as the float-"
                "weight window (params are never donated)")
def _build_serving_decode_step_w8():
    import jax
    from apex_tpu import serving
    cfg, params, spec, arena = _serving_fixture()
    wp = serving.quantize_serving_params(params, "int8")
    state = serving.init_state(arena, window=2)
    fn = serving.decode_window_fn(cfg, spec, window=2)
    return {
        "fn": fn, "args": (wp, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            # same 17 (leaves - 2) as serving.decode_step: weight
            # quantization changes the params operand, not the carry
            "donated_aliases": 17,
            # 6 matmul weight planes x 2 layers, counted once in the
            # fori body; no quantize converts anywhere in the step
            "int8_convert_counts": {"to_int8": 0, "from_int8": 12},
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "serving.spec_decode_step_quantized",
    anchor="apex_tpu/serving/steps.py",
    description="speculative decode window at int8 KV x int8 weights "
                "(the full memory-frontier stack): cast economy pinned "
                "on BOTH sides — per layer, the verify insert round-"
                "trips its fresh K/V through arena storage semantics "
                "(2 to_int8 + 2 from_int8 each of 2 layers) on top of "
                "the window's one dequantize-gather (2) and one "
                "quantize-scatter (2), plus 6 weight dequants per "
                "layer — and still zero host traffic")
def _build_serving_spec_decode_step_quantized():
    import jax
    from apex_tpu import serving
    cfg, params, spec, arena = _serving_fixture(kv_dtype="int8")
    wp = serving.quantize_serving_params(params, "int8")
    state = serving.init_state(arena, window=2, spec_k=2)
    fn = serving.decode_window_fn(cfg, spec, window=2, spec_k=2)
    return {
        "fn": fn, "args": (wp, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            # same 15 as the float spec window (the scale planes
            # alias — the scatter genuinely updates them)
            "donated_aliases": 15,
            # to_int8: 2 scatter + 2/layer x 2 verify round-trip = 6;
            # from_int8: 2 gather + 2/layer x 2 round-trip
            #            + 6/layer x 2 weights = 18
            "int8_convert_counts": {"to_int8": 6, "from_int8": 18},
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "serving.prefill_batched",
    anchor="apex_tpu/serving/steps.py",
    description="batched multi-request prefill: B queued prompts "
                "drain through ONE padded-bucket program call — one "
                "flash-attention pallas_call per decoder layer for the "
                "whole group, K/V pages scattered into the DONATED "
                "arena (all four arena buffers aliased), per-request "
                "first tokens sampled device-side, zero host traffic")
def _build_serving_prefill_batched():
    import jax
    import jax.numpy as jnp
    from apex_tpu import serving
    from apex_tpu.ops._dispatch import op_enabled
    cfg, params, spec, arena = _serving_fixture()
    nb, bucket = 2, 8
    fn = serving.prefill_batch_fn(cfg, spec, bucket, nb)
    args = (params, arena.k, arena.v, arena.k_scale, arena.v_scale,
            jnp.zeros((nb, bucket // spec.page_size), jnp.int32),
            jnp.zeros((nb, bucket), jnp.int32),
            jnp.full((nb,), 5, jnp.int32),
            jnp.zeros((nb, 2), jnp.uint32),
            jnp.zeros((nb,), jnp.float32),
            jnp.zeros((nb,), jnp.int32),
            jnp.ones((nb,), jnp.float32))
    expect = {
        "no_host_transfer": True,
        "no_f64": True,
        # the K and V arenas plus both scale planes, exactly as the
        # serial serving.prefill_step
        "donated_aliases": 4,
        "no_orphan_collectives": True,
    }
    if op_enabled("attention_f32"):   # dispatch-gate aware, like optim
        expect["pallas_calls"] = cfg.n_layers
    return {"fn": fn, "args": args,
            "jit_kwargs": {"donate_argnums": (1, 2, 3, 4)},
            "expect": expect}


@register_spec(
    "serving.traced_decode_step",
    anchor="apex_tpu/serving/engine.py",
    description="request tracing is free on device: a decode window "
                "traced WHILE a live RequestTracer records enqueue/"
                "admit/decode-window events lowers to the exact same "
                "program as the untraced spec — zero transfer or "
                "callback prims added, donation arity unchanged (the "
                "tracer is host-side bookkeeping only)")
def _build_serving_traced_decode_step():
    import jax
    from apex_tpu import serving
    from apex_tpu.telemetry.reqtrace import RequestTracer
    cfg, params, spec, arena = _serving_fixture()
    state = serving.init_state(arena, window=2)
    fn = serving.decode_window_fn(cfg, spec, window=2)
    tracer = RequestTracer(host=0)

    def traced(params, state):
        # Live tracer bookkeeping exactly as the engine interleaves
        # it around the device call — all host-side, so it must not
        # contribute a single prim to the lowered program.
        tracer.enqueue("spec-req", t=0.0)
        tracer.admit("spec-req", window=0, slot=0, mode="prefill",
                     queue_ms=0.0, t=0.0)
        out = fn(params, state)
        tracer.decode_window("spec-req", 1, 2, t=0.0)
        return out

    updated = len(jax.tree_util.tree_leaves(state)) - 2
    return {
        "fn": traced, "args": (params, state),
        "jit_kwargs": {"donate_argnums": (1,)},
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            # identical donation arity to serving.decode_step —
            # tracing changed nothing in the program
            "donated_aliases": updated,
            "no_orphan_collectives": True,
        },
    }


@register_spec(
    "ddp.all_reduce_flat_buffers",
    anchor="apex_tpu/parallel/distributed.py",
    description="bucket-granular DDP all-reduce under shard_map: "
                "exactly one psum per flat bucket, every collective "
                "bound to the declared axis, none dead")
def _build_all_reduce_flat():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu import comm
    from apex_tpu.parallel.distributed import all_reduce_flat_buffers

    mesh = Mesh(np.array(jax.devices()[:1]), (comm.AXIS_DATA,))
    bufs = (jnp.ones((256,), jnp.float32),
            jnp.ones((128,), jnp.float32))

    def reduce(bufs):
        return tuple(all_reduce_flat_buffers(list(bufs),
                                             comm.AXIS_DATA))

    fn = comm.shard_map(reduce, mesh, in_specs=(P(),), out_specs=P())
    return {
        "fn": fn, "args": (bufs,),
        "expect": {
            "no_host_transfer": True,
            "no_f64": True,
            "psum_count": len(bufs),
            "collective_axes": {comm.AXIS_DATA},
            "no_orphan_collectives": True,
        },
        # apexcost: this card's static collective bytes become the
        # extra.ddp_collective_bytes_per_step perf-budget row and are
        # cross-checked against ddp/bytes_allreduced telemetry
        "cost_meta": {"ddp_step": True},
    }
