"""amp.initialize and the amp serialization contract, JAX-native.

Reference: apex/amp/frontend.py + _initialize.py + _amp_state.py
(SURVEY.md §3.1).  The reference mutates torch models/optimizers in place
(weight casts, forward patching, optimizer.step patching).  The JAX
contract is functional: ``initialize`` takes a params pytree, returns the
cast params plus an ``AmpState`` carrying the policy, optional f32
masters, and the loss-scaler state; train steps thread AmpState through.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from apex_tpu.amp.policies import (Policy, Properties, opt_level_properties)
from apex_tpu.amp.scaler import (LossScaleConfig, LossScaleState,
                                 re_anchor, update_state)
from apex_tpu.amp.wrap import auto_cast, cast_inputs
from apex_tpu.telemetry.retrace import phased
from apex_tpu.telemetry.spans import span

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AmpState:
    """Carried amp state (a pytree; static config in `properties`)."""
    master_params: Optional[Pytree]
    scaler: LossScaleState
    properties: Properties = dataclasses.field(
        metadata=dict(static=True), default_factory=Properties)
    scaler_config: LossScaleConfig = dataclasses.field(
        metadata=dict(static=True), default_factory=LossScaleConfig)

    @property
    def policy(self) -> Policy:
        return self.properties.policy(self._half_dtype())

    def wrap_forward(self, fn, cast_argnums=None):
        """Apply this opt level's casting mechanism to an UNMODIFIED
        forward function — the reference's model-patching step
        (apex/amp/_initialize.py) as a functional wrapper.

        O1 (patch_torch_functions): the trace-time op-list rewriter.
        O2/O3 (cast_model_type set): cast floating inputs (restricted to
        ``cast_argnums`` positions if given — the data args) to the
        model half dtype.  O0 / disabled: identity.
        """
        props = self.properties
        if not props.enabled:
            return fn
        if props.patch_torch_functions:
            return auto_cast(fn, self.policy)
        if props.cast_model_type is not None:
            return cast_inputs(fn, props.cast_model_type, cast_argnums)
        return fn

    def _half_dtype(self):
        cast = self.properties.cast_model_type
        return cast if cast is not None else jnp.bfloat16

    @property
    def fp8_policy(self):
        """The armed :class:`apex_tpu.amp.fp8.Fp8Policy` (None when
        this state was initialized without ``fp8=``) — hand it to
        fp8-capable modules (``FusedDense(fp8=state.fp8_policy)``,
        the tensor-parallel linears)."""
        return getattr(self.properties, "fp8", None)

    def flat_pipeline(self, optimizer=None, plan=None,
                      max_grad_norm: float = 0.0, axis_name=None,
                      **kw):
        """A :class:`~apex_tpu.amp.flat_pipeline.FlatGradPipeline` for
        this amp state — the pack-once gradient path (one fused
        unscale+norm+clip kernel per bucket, bucket-granular
        all-reduce) feeding a bucketed fused optimizer.  Call its
        ``scaled_value_and_grad(loss_fn, amp_state_or_scaler, ...)``
        with this state's ``scaler`` threaded through the train step.
        """
        from apex_tpu.amp.flat_pipeline import FlatGradPipeline
        kw.setdefault("fp8", self.fp8_policy)
        return FlatGradPipeline(optimizer=optimizer, plan=plan,
                                max_grad_norm=max_grad_norm,
                                axis_name=axis_name, **kw)

    def re_anchor(self, scale=None) -> "AmpState":
        """This state with its scaler reset to a known-safe operating
        point (:func:`apex_tpu.amp.scaler.re_anchor`) — the watchdog's
        quarantine action after a NaN storm or scale collapse."""
        return dataclasses.replace(
            self, scaler=re_anchor(self.scaler, self.scaler_config,
                                   scale))

    def telemetry_values(self) -> dict:
        """This state's scaler scalars under their standard telemetry
        names (still on device — no sync), ready for
        ``Telemetry.record`` in eager train loops; jitted steps get
        the same names for free via the producers inside
        ``scaled_value_and_grad``/``update_state``."""
        return {"amp/loss_scale": self.scaler.loss_scale,
                "amp/growth_tracker": self.scaler.growth_tracker,
                "amp/found_inf": self.scaler.found_inf}

    # --- apex serialization contract: amp.state_dict() round-trips the
    # loss scaler (scale + unskipped count), frontend.py parity ---
    def state_dict(self):
        return {
            "loss_scaler0": {
                "loss_scale": float(self.scaler.loss_scale),
                "unskipped": int(self.scaler.growth_tracker),
            }
        }

    def load_state_dict(self, sd):
        entry = sd.get("loss_scaler0", {})
        return dataclasses.replace(
            self,
            scaler=LossScaleState(
                loss_scale=jnp.float32(entry.get("loss_scale",
                                                 self.scaler_config.init_scale)),
                growth_tracker=jnp.int32(entry.get("unskipped", 0)),
                found_inf=jnp.int32(0),
            ))


# the casts of the model's tree: one small program a distinct leaf shape
@phased("apex/amp/initialize")
def initialize(params: Pytree,
               opt_level: str = "O1",
               half_dtype=jnp.bfloat16,
               cast_model_type=None,
               keep_batchnorm_fp32=None,
               master_weights=None,
               loss_scale: Union[str, float, None] = None,
               enabled: bool = True,
               fp8=None,
               ) -> Tuple[Pytree, AmpState]:
    """Resolve an opt level to a precision configuration and cast params.

    Mirrors apex.amp.initialize's signature shape (model, optimizers →
    params pytree here); per-kwarg overrides beat the table defaults, as in
    the reference.  Returns (cast_params, amp_state).

    ``fp8`` (beyond-reference): an ``amp.fp8.Fp8Policy`` (or ``True``
    for the autotuned defaults) arms the fp8 training path on top of
    the opt level — matmul-shaped modules built with
    ``fp8=state.fp8_policy`` quantize to e4m3 forward / e5m2 backward
    under delayed scaling, and ``state.flat_pipeline()`` threads the
    packed per-bucket scale state (docs/amp.md "fp8 training").
    Params still cast per the opt level (fp8 is a COMPUTE format, not
    a storage format — weights stay bf16/f16 masters-backed).
    """
    props = opt_level_properties(opt_level, half_dtype)
    if cast_model_type is not None:
        props.cast_model_type = cast_model_type
    if keep_batchnorm_fp32 is not None:
        props.keep_batchnorm_fp32 = keep_batchnorm_fp32
    if master_weights is not None:
        props.master_weights = master_weights
    if loss_scale is not None:
        props.loss_scale = loss_scale
    props.enabled = enabled
    if fp8 is not None and fp8 is not False:
        from apex_tpu.amp.fp8 import Fp8Policy, tuned_policy
        if fp8 is True:
            fp8 = tuned_policy()
        if not isinstance(fp8, Fp8Policy):
            raise TypeError(
                f"fp8= expects an amp.fp8.Fp8Policy or True, got "
                f"{type(fp8).__name__}")
        props.fp8 = fp8
    if not enabled:
        return params, AmpState(master_params=None,
                                scaler=LossScaleState.create(1.0),
                                properties=props,
                                scaler_config=LossScaleConfig(dynamic=False))

    masters = None
    cast_params = params
    if props.cast_model_type is not None:
        cast_params = jax.tree_util.tree_map(
            lambda x: x.astype(props.cast_model_type)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        if props.master_weights:
            masters = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    dynamic = props.loss_scale == "dynamic"
    init_scale = 2.0 ** 16 if dynamic else float(props.loss_scale)
    cfg = LossScaleConfig(init_scale=init_scale, dynamic=dynamic)
    scaler = LossScaleState.create(init_scale)
    return cast_params, AmpState(master_params=masters, scaler=scaler,
                                 properties=props, scaler_config=cfg)


def master_params_to_model_params(model_params: Pytree,
                                  master_params: Pytree) -> Pytree:
    """Copy f32 masters back into the model-dtype params (O2 step tail).

    Reference: apex/amp/_process_optimizer.py::_master_params_to_model_params.
    """
    return jax.tree_util.tree_map(
        lambda mp, m: m.astype(mp.dtype), model_params, master_params)


def update_scaler(state: AmpState, found_inf, skipped=None) -> AmpState:
    """``skipped``: the step was skipped externally (watchdog
    quarantine) — the growth tracker holds instead of counting the
    window as clean (:func:`apex_tpu.amp.scaler.update_state`)."""
    with span("apex/amp/update_scaler"):
        return dataclasses.replace(
            state, scaler=update_state(state.scaler,
                                       jnp.asarray(found_inf, jnp.int32),
                                       state.scaler_config,
                                       skipped=skipped))


def state_dict(*states: AmpState) -> dict:
    """Serialize N amp states as the reference's multi-scaler layout.

    apex's ``amp.initialize(..., num_losses=N)`` keeps N scalers and
    ``amp.state_dict()`` emits ``{'loss_scaler0': ..., 'loss_scalerN':
    ...}`` (frontend.py).  The functional analog of num_losses is one
    AmpState per loss (see examples/dcgan); this helper merges them into
    the same reference-shaped dict so checkpoints port unchanged.
    """
    out = {}
    for i, s in enumerate(states):
        out[f"loss_scaler{i}"] = s.state_dict()["loss_scaler0"]
    return out


def load_state_dict(sd: dict, *states: AmpState):
    """Inverse of ``state_dict(*states)``: returns the restored states
    (a single AmpState when one was passed, else a tuple in order).
    Warns on a scaler-count mismatch (reference behavior) — missing
    entries leave that state's scaler at its config default."""
    import warnings
    saved = sum(1 for k in sd if k.startswith("loss_scaler"))
    if saved != len(states):
        warnings.warn(
            f"amp.load_state_dict: checkpoint has {saved} loss scaler(s) "
            f"but {len(states)} AmpState(s) were passed; unmatched "
            "states keep their initial scale", stacklevel=2)
    restored = tuple(
        s.load_state_dict({"loss_scaler0": sd.get(f"loss_scaler{i}", {})})
        for i, s in enumerate(states))
    return restored[0] if len(restored) == 1 else restored
