"""Dynamic loss scaling as carried pytree state.

Reference: apex/amp/scaler.py + csrc/update_scale_hysteresis.cu
(SURVEY.md §2.1, §3.2).  Semantics preserved: scale the loss before
backward; unscale grads; if any grad is non-finite, skip the step and
multiply the scale by ``backoff_factor`` (0.5); after ``growth_interval``
(2000) consecutive clean steps multiply by ``growth_factor`` (2.0).

TPU redesign: the reference reads the overflow flag on the host every step
(a device sync).  Here the flag, the skip decision (lax.cond) and the
scale update are all traced into the jitted train step; the scaler state
is a pytree the caller threads through.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.telemetry import _tape

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LossScaleState:
    """Carried state of one dynamic loss scaler (a pytree)."""
    loss_scale: jax.Array      # f32 scalar
    growth_tracker: jax.Array  # i32 scalar: consecutive clean steps
    found_inf: jax.Array       # i32 scalar: last step's overflow flag

    @staticmethod
    def create(init_scale: float = 2.0 ** 16) -> "LossScaleState":
        return LossScaleState(
            loss_scale=jnp.float32(init_scale),
            growth_tracker=jnp.int32(0),
            found_inf=jnp.int32(0),
        )


@dataclasses.dataclass(frozen=True)
class LossScaleConfig:
    init_scale: float = 2.0 ** 16
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    min_loss_scale: float = 1.0
    max_loss_scale: float = 2.0 ** 24
    dynamic: bool = True


@jax.named_scope("apex_amp/scale_loss")
def scale_loss(loss: jax.Array, state: LossScaleState) -> jax.Array:
    return loss * state.loss_scale.astype(loss.dtype)


@jax.named_scope("apex_amp/unscale")
def unscale_grads(grads: Pytree, state: LossScaleState) -> Pytree:
    inv = 1.0 / state.loss_scale
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads)


@jax.named_scope("apex_amp/unscale")
def check_finite(grads: Pytree) -> jax.Array:
    """i32 flag: 1 iff any grad element is non-finite.  Stays on device."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.int32(0)
    bad = jnp.stack([
        jnp.logical_not(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
        for g in leaves])
    return jnp.any(bad).astype(jnp.int32)


def update_state(state: LossScaleState, found_inf: jax.Array,
                 config: LossScaleConfig = LossScaleConfig(),
                 skipped=None) -> LossScaleState:
    """update_scale_hysteresis semantics, branch-free on device.

    ``skipped`` (optional i32/bool, traced or concrete): the step was
    skipped EXTERNALLY — a watchdog quarantine, a pipeline bubble —
    rather than by the scaler's own overflow logic.  Such a step is
    neither a clean step nor an overflow: the growth tracker must not
    advance toward the growth interval (it did not observe a clean
    optimizer update) and the scale must not move.  Without the flag a
    quarantined window would count toward ``growth_interval`` and the
    scale could grow across a window where nothing was learned.
    """
    if not config.dynamic:
        _tape.emit("amp/found_inf", found_inf, reduce="max")
        return dataclasses.replace(state, found_inf=found_inf)
    overflowed = found_inf > 0
    tracker = jnp.where(overflowed, 0, state.growth_tracker + 1)
    grow = tracker >= config.growth_interval
    new_scale = jnp.where(
        overflowed,
        jnp.maximum(state.loss_scale * config.backoff_factor,
                    config.min_loss_scale),
        jnp.where(grow,
                  jnp.minimum(state.loss_scale * config.growth_factor,
                              config.max_loss_scale),
                  state.loss_scale),
    )
    tracker = jnp.where(grow, 0, tracker)
    if skipped is not None:
        ext = jnp.asarray(skipped, jnp.int32) > 0
        new_scale = jnp.where(ext, state.loss_scale, new_scale)
        tracker = jnp.where(ext, state.growth_tracker, tracker)
    # telemetry (no-ops without an active tape): a collapsing loss
    # scale is THE amp failure signature worth watching live
    _tape.emit("amp/loss_scale", new_scale)
    _tape.emit("amp/growth_tracker", tracker)
    _tape.emit("amp/found_inf", found_inf, reduce="max")
    return LossScaleState(
        loss_scale=new_scale,
        growth_tracker=tracker,
        found_inf=found_inf,
    )


def re_anchor(state: LossScaleState,
              config: LossScaleConfig = LossScaleConfig(),
              scale=None) -> LossScaleState:
    """Reset the scaler to a known-safe operating point — the
    watchdog's quarantine action.

    After a detected training anomaly (NaN storm that outlasted the
    backoff, loss-scale collapse) the scaler's carried state is part of
    the damage: the scale may be pinned at the floor and the growth
    tracker mid-count.  ``re_anchor`` returns a fresh state at
    ``scale`` (default: the config's init scale), tracker zeroed,
    overflow flag cleared — so recovery restarts from the configured
    operating point instead of crawling back up by growth intervals.
    """
    if scale is None:
        scale = config.init_scale
    return LossScaleState(
        loss_scale=jnp.float32(scale),
        growth_tracker=jnp.int32(0),
        found_inf=jnp.int32(0),
    )


def scaled_value_and_grad(loss_fn, state: LossScaleState, *args,
                          has_aux: bool = False, grads_layout: str = "tree",
                          plan=None, microbatches: int = 1, **kwargs):
    """value_and_grad of a LOSS-SCALED objective, then unscale.

    The canonical TPU replacement for the reference's
    ``with amp.scale_loss(loss, optimizer) as scaled: scaled.backward()``
    idiom (apex/amp/handle.py): grads come back already unscaled plus the
    on-device found_inf flag for the conditional optimizer step.

    Returns ((loss, aux?), grads, found_inf).

    ``grads_layout="flat"`` switches the gradient side to the flat
    pipeline: grads come back as an ``amp.FlatGrads`` bundle — packed
    ONCE into per-bucket flat buffers (``plan``: a BucketPlan, a
    bucketed fused optimizer, or None to derive a cached plan from the
    grads), unscaled by one fused kernel per bucket that also yields
    the global norm and the overflow flag.  The per-leaf ``"tree"``
    layout stays the oracle.

    ``microbatches=N`` (N > 1) splits every batch argument
    (``args[1:]``) along its leading axis and accumulates gradients
    across a scan before unscaling ONCE by ``1/(loss_scale * N)`` (the
    mean-over-global-batch convention), with the overflow flag latched
    across microbatches.  On the flat layout the accumulation is the
    fused per-bucket ``flat_accumulate`` path (zero per-leaf work —
    docs/amp.md "Gradient accumulation"); on the tree layout it is the
    per-leaf f32 oracle of the same schedule.  With ``has_aux`` the
    aux comes back stacked along a leading microbatch axis.
    """
    if grads_layout not in ("tree", "flat"):
        raise ValueError(f"unknown grads_layout {grads_layout!r}")
    if grads_layout == "flat":
        # layering: flat_pipeline imports this module; import lazily
        from apex_tpu.amp.flat_pipeline import FlatGradPipeline
        if plan is not None and not hasattr(plan, "pack_grads"):
            pipe = FlatGradPipeline(optimizer=plan)   # a fused optimizer
        else:
            # plan=None: the pipeline derives a cached plan from the
            # gradient tree at first pack
            pipe = FlatGradPipeline(plan=plan, defer_plan=plan is None)
        out, flat = pipe.scaled_value_and_grad(
            loss_fn, state, *args, has_aux=has_aux,
            microbatches=microbatches, **kwargs)
        return out, flat, flat.found_inf

    def scaled_fn(*a, **kw):
        out = loss_fn(*a, **kw)
        if has_aux:
            loss, aux = out
            return scale_loss(loss, state), aux
        return scale_loss(out, state)

    if microbatches > 1:
        return _microbatched_tree(scaled_fn, state, args, has_aux,
                                  int(microbatches), kwargs)

    if has_aux:
        (scaled, aux), grads = jax.value_and_grad(
            scaled_fn, has_aux=True)(*args, **kwargs)
    else:
        scaled, grads = jax.value_and_grad(scaled_fn)(*args, **kwargs)
        aux = None
    found_inf = check_finite(grads)
    grads = unscale_grads(grads, state)
    with jax.named_scope("apex_amp/unscale"):
        loss = scaled / state.loss_scale
    _tape.emit("amp/found_inf", found_inf, reduce="max")
    _tape.emit("amp/loss_scale", state.loss_scale)
    _tape.emit("loss", loss)
    if has_aux:
        return (loss, aux), grads, found_inf
    return loss, grads, found_inf


def split_microbatch_args(args, n: int):
    """``(params, stacked-batch)`` from a microbatched call's args:
    every argument after the params (args[0]) splits ``(n, lead/n,
    ...)`` along its leading axis — the ONE splitting contract shared
    by the per-leaf oracle below and FlatGradPipeline's fused path."""
    if len(args) < 2:
        raise ValueError(
            "microbatches=N needs batch arguments after the params "
            "(they are split along their leading axis)")
    params, *batch = args
    leads = {tuple(getattr(a, "shape", ()))[:1]
             for a in jax.tree_util.tree_leaves(tuple(batch))}
    if () in leads or len(leads) != 1:
        # a 0-d arg (step scalar, key) or mismatched leading dims
        # would silently mis-split into wrong per-microbatch slices —
        # every split arg must share ONE batch axis
        raise ValueError(
            "microbatches=N splits every argument after the params "
            "along a shared leading batch axis, but the batch "
            f"arguments have leading dims {sorted(leads)} — close "
            "over non-batch values instead of passing them "
            "positionally")

    def split(a):
        if a.shape[0] % n:
            raise ValueError(
                f"microbatches={n} does not divide the leading batch "
                f"axis of shape {a.shape}")
        return a.reshape((n, a.shape[0] // n) + tuple(a.shape[1:]))

    return params, jax.tree_util.tree_map(split, tuple(batch))


def _microbatched_tree(scaled_fn, state, args, has_aux, n, kwargs):
    """Per-leaf microbatch accumulation (the tree-layout oracle of
    FlatGradPipeline's fused path): scan over leading-axis splits,
    accumulate SCALED grads in f32 per leaf, unscale once by
    ``1/(loss_scale * n)``, latch found_inf across microbatches."""
    params, xs = split_microbatch_args(args, n)

    def wrapped(p, *b):
        out = scaled_fn(p, *b, **kwargs)
        return out if has_aux else (out, None)

    def body(carry, micro):
        acc, scaled_sum, bad = carry
        (scaled, aux), grads = jax.value_and_grad(
            wrapped, has_aux=True)(params, *micro)
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads)
        bad = jnp.maximum(bad, check_finite(acc))
        return (acc, scaled_sum + scaled, bad), aux

    acc0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (acc, scaled_sum, found_inf), auxes = jax.lax.scan(
        body, (acc0, jnp.float32(0.0), jnp.int32(0)), xs)
    with jax.named_scope("apex_amp/unscale"):
        inv = 1.0 / (state.loss_scale * jnp.float32(n))
        grads = jax.tree_util.tree_map(
            lambda a, p: (a * inv).astype(p.dtype), acc, params)
        loss = scaled_sum / (jnp.float32(n) * state.loss_scale)
    _tape.emit("amp/found_inf", found_inf, reduce="max")
    _tape.emit("amp/loss_scale", state.loss_scale)
    _tape.emit("loss", loss)
    if has_aux:
        return (loss, auxes), grads, found_inf
    return loss, grads, found_inf


def conditional_step(state: LossScaleState, found_inf: jax.Array,
                     step_fn, params: Pytree, opt_state: Pytree,
                     config: LossScaleConfig = LossScaleConfig()
                     ) -> Tuple[Pytree, Pytree, LossScaleState]:
    """Apply ``step_fn(params, opt_state) -> (params, opt_state)`` only when
    grads were finite; always update scaler state.  The skip is a
    lax.cond — no host sync (contrast: reference optimizer.step patching in
    apex/amp/_process_optimizer.py reads the flag on host)."""
    def do_step(operand):
        p, s = operand
        return step_fn(p, s)

    def skip(operand):
        return operand

    params, opt_state = jax.lax.cond(
        found_inf == 0, do_step, skip, (params, opt_state))
    return params, opt_state, update_state(state, found_inf, config)


class LossScaler:
    """Reference-shaped stateful facade over the functional core
    (apex/amp/scaler.py::LossScaler).  Host-side convenience only; jitted
    code should use the functional API above."""

    def __init__(self, loss_scale="dynamic", init_scale=2.0 ** 16,
                 scale_factor=2.0, scale_window=2000,
                 min_loss_scale=None, max_loss_scale=2.0 ** 24):
        self._dynamic = loss_scale == "dynamic"
        init = init_scale if self._dynamic else float(loss_scale)
        self.config = LossScaleConfig(
            init_scale=init,
            growth_factor=scale_factor,
            backoff_factor=1.0 / scale_factor,
            growth_interval=scale_window,
            min_loss_scale=min_loss_scale if min_loss_scale is not None else 1.0,
            max_loss_scale=max_loss_scale,
            dynamic=self._dynamic,
        )
        self.state = LossScaleState.create(init)

    def loss_scale(self):
        return float(self.state.loss_scale)

    def scale(self, loss):
        return scale_loss(loss, self.state)

    def unscale(self, grads):
        return unscale_grads(grads, self.state)

    def update_scale(self, found_inf):
        self.state = update_state(self.state,
                                  jnp.asarray(found_inf, jnp.int32),
                                  self.config)

    # apex serialization contract (amp.state_dict round-trips scaler state)
    def state_dict(self):
        return {
            "loss_scale": float(self.state.loss_scale),
            "unskipped": int(self.state.growth_tracker),
        }

    def load_state_dict(self, sd):
        self.state = LossScaleState(
            loss_scale=jnp.float32(sd["loss_scale"]),
            growth_tracker=jnp.int32(sd.get("unskipped", 0)),
            found_inf=jnp.int32(0),
        )
