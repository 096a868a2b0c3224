"""fp8 training: delayed-scaling policy + packed per-bucket state.

The reference apex stops at fp16/bf16; fp8-capable TPUs run
e4m3/e5m2 matmuls at roughly 2x the bf16 MXU rate, and the flat AMP
pipeline already owns everything delayed scaling needs: per-bucket
flat buffers, per-tensor reduces over static segments, the loss scaler's
growth/backoff discipline and the watchdog's rollback safety net.

Design (the transformer-engine recipe, bucketized):

- **Formats**: e4m3 forward (max 448 — precision over range),
  e5m2 backward (max 57344 — gradients need range).  Where the
  backend has no fp8 matmul the COMPUTE falls back to bf16 while the
  quantization (convert to the fp8 storage dtype) still runs, so the
  scaling discipline — and every bit of the amax/scale bookkeeping —
  is identical on CPU tier-1 and on hardware ("bf16-compute oracle").
- **Delayed scaling**: tensors are quantized with the PREVIOUS steps'
  scale while the current step only records amax; the scale is
  recomputed from a rolling per-tensor amax history
  (``fp8_max / (2**margin * max(history))``).  No dependency of this
  step's quantization on this step's values = no extra serialization.
- **Packed state**: the per-tensor amax history and scale live packed
  in the :class:`~apex_tpu.multi_tensor_apply.packer.BucketPlan`
  layout — one ``(n_leaves, H)`` history matrix and one
  ``(n_leaves,)`` scale vector per bucket — updated by ONE flat pass
  per bucket (``ops.multi_tensor.flat_amax_scale_update``: per-
  segment amax + history roll + scale recompute + per-tensor overflow
  backoff), never a per-leaf tree_map.  As optimizer slots
  (``FusedOptimizerBase.enable_fp8``) the state is donated, offloaded,
  checkpointed and re-chunked like every other slot.
- **Overflow**: a non-finite amax latches ``found_inf`` — the step is
  skipped branch-free and the step clock holds, exactly like a loss-
  scale overflow — while the affected tensor's scale backs off by
  ``backoff_factor`` (the scaler's hysteresis, layered per bucket).
  A scale pinned at its floor is the fp8 collapse signature the
  watchdog's :class:`~apex_tpu.resilience.watchdog.
  Fp8ScaleCollapseDetector` watches (``fp8/scale_min``).

See docs/amp.md "fp8 training" for the state layout and the fallback
matrix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply.packer import BucketPlan
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.telemetry import _tape

Pytree = Any

#: fp8 format maxima (jnp.finfo where the dtypes exist; these are the
#: IEEE-P3109/OCP values and never change).
E4M3_MAX = 448.0
E5M2_MAX = 57344.0

_DTYPES = {"e4m3": ("float8_e4m3fn", E4M3_MAX),
           "e5m2": ("float8_e5m2", E5M2_MAX)}


def fp8_dtype(which: str):
    """The jnp fp8 dtype for ``which`` ("e4m3"/"e5m2"), or None where
    this jax build lacks it (the storage-level availability gate)."""
    name, _ = _DTYPES[which]
    return getattr(jnp, name, None)


def fp8_max(which: str) -> float:
    return _DTYPES[which][1]


@functools.lru_cache(maxsize=None)
def fp8_matmul_available() -> bool:
    """True iff the default backend can COMPILE every fp8 dot the
    training path emits: e4m3 x e4m3 (forward) AND the mixed
    e5m2 x e4m3 / e4m3 x e5m2 combinations the backward's shared
    cotangent produces — a backend that accepts the forward but
    rejects the mixed backward dots must fall back as a whole, or the
    first ``jax.grad`` would fail at compile time.

    Probed once with a tiny lowering+compile; failure (old chip
    generations, jax builds without fp8) routes ``fp8_matmul``'s
    compute to the bf16 fallback while the quantization and scale
    bookkeeping run unchanged."""
    e4 = fp8_dtype("e4m3")
    e5 = fp8_dtype("e5m2")
    if e4 is None or e5 is None:
        return False
    try:
        a4 = jax.ShapeDtypeStruct((8, 8), e4)
        a5 = jax.ShapeDtypeStruct((8, 8), e5)

        def probe(x4, g5):
            dot = functools.partial(
                jax.lax.dot_general,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dot(x4, x4), dot(g5, x4), dot(x4, g5)

        jax.jit(probe).lower(a4, a5).compile()
        return True
    except Exception:
        return False


@dataclasses.dataclass(frozen=True)
class Fp8Policy:
    """Static fp8 training configuration (hashable — safe to close
    over in jitted code and to use as a custom_vjp nondiff arg).

    ``fwd_format``/``bwd_format``: fp8 formats for forward operands
    (activations/weights) and backward cotangents.  ``amax_history_len``
    and ``interval`` are the delayed-scaling cadence knobs the
    autotuner sweeps (``tools/autotune.py``; build with
    :func:`tuned_policy` to pick up the measured per-topology values).
    ``margin``: extra headroom exponent in the scale formula.
    ``compute``: "auto" uses real fp8 matmuls where the backend
    compiles them, else the bf16-compute oracle; "fp8"/"bf16" force
    either side (tests pin "bf16" to assert the bookkeeping is
    bit-identical across compute paths).
    """
    fwd_format: str = "e4m3"
    bwd_format: str = "e5m2"
    amax_history_len: int = 16
    interval: int = 1
    margin: float = 0.0
    backoff_factor: float = 0.5
    compute: str = "auto"

    def __post_init__(self):
        for f in (self.fwd_format, self.bwd_format):
            if f not in _DTYPES:
                raise ValueError(f"unknown fp8 format {f!r}; one of "
                                 f"{sorted(_DTYPES)}")
        if self.amax_history_len < 1:
            raise ValueError("amax_history_len must be >= 1")
        if self.interval < 1:
            raise ValueError("interval must be >= 1")
        if self.compute not in ("auto", "fp8", "bf16"):
            raise ValueError(f"unknown compute {self.compute!r}")

    def fwd_dtype(self):
        return fp8_dtype(self.fwd_format)

    def bwd_dtype(self):
        return fp8_dtype(self.bwd_format)

    def fwd_max(self) -> float:
        return fp8_max(self.fwd_format)

    def bwd_max(self) -> float:
        return fp8_max(self.bwd_format)

    def uses_fp8_compute(self) -> bool:
        """Whether matmuls run on fp8 operands (vs the bf16-compute
        oracle).  Requires the storage dtypes to exist either way."""
        if self.fwd_dtype() is None or self.bwd_dtype() is None:
            return False
        if self.compute == "fp8":
            return True
        if self.compute == "bf16":
            return False
        return fp8_matmul_available()


def tuned_policy(**overrides) -> Fp8Policy:
    """An :class:`Fp8Policy` with the autotuner's measured per-topology
    scaling cadence applied (``fp8.amax_history_len`` /
    ``fp8.interval`` from the dispatch prefs table — the design
    defaults where no sweep recorded one).  Explicit ``overrides``
    always win."""
    from apex_tpu.ops import _dispatch
    kw = {}
    h = _dispatch.fp8_pref("amax_history_len")
    if h is not None:
        kw["amax_history_len"] = int(h)
    n = _dispatch.fp8_pref("interval")
    if n is not None:
        kw["interval"] = int(n)
    kw.update(overrides)
    return Fp8Policy(**kw)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Fp8State:
    """Packed delayed-scaling state over one BucketPlan (a pytree).

    ``amax_history``: per bucket, (n_leaves, H) f32 — row per tensor,
    column 0 newest.  ``scale``: per bucket, (n_leaves,) f32 — the
    CURRENT quantization scales (``value * scale`` fills the fp8
    range; dequantize multiplies by ``1/scale``).  ``step``: i32
    update counter driving the scale-update-interval cadence.
    """
    amax_history: List[jax.Array]
    scale: List[jax.Array]
    step: jax.Array


def init_state(plan: BucketPlan, policy: Fp8Policy) -> Fp8State:
    """Fresh state: zero history, unit scales."""
    h = policy.amax_history_len
    return Fp8State(
        amax_history=[jnp.zeros((len(b.leaves), h), jnp.float32)
                      for b in plan.buckets],
        scale=[jnp.ones((len(b.leaves),), jnp.float32)
               for b in plan.buckets],
        step=jnp.int32(0))


def update_state(state: Fp8State, bufs: Sequence[jax.Array],
                 plan: BucketPlan, policy: Fp8Policy, *,
                 fp8_max_value: Optional[float] = None,
                 skip=None, telemetry_prefix: str = "fp8"
                 ) -> Tuple[Fp8State, jax.Array]:
    """Roll this step's per-tensor amax into the packed state: ONE
    flat pass per bucket (``mt.flat_amax_scale_update``).  Returns
    ``(new_state, found_inf)`` — found_inf flags any non-finite amax
    and must be OR'd into the step's skip flag (the fp8 analog of the
    loss scaler's overflow latch; the step clock holds with it).

    ``skip`` (traced bool/i32 ok): an externally-skipped step — the
    CLEAN transition holds (no history roll, no scale recompute),
    mirroring ``amp.update_state(skipped=)``; the scale-update-
    interval cadence (``policy.interval``) composes the same way, and
    amax from a gated step is simply not recorded (delayed scaling
    tolerates sparse histories by construction).  A tensor whose amax
    OVERFLOWED still backs off on a gated step — overflow response
    must not wait for the cadence, exactly like the loss scaler backs
    off on the steps it skips — and is transient by construction: the
    next clean update RECOMPUTES the scale from the (unpoisoned)
    history rather than incrementally recovering it.
    """
    do = jnp.equal(state.step % jnp.int32(policy.interval), 0)
    if skip is not None:
        do = jnp.logical_and(do,
                             jnp.asarray(skip, jnp.int32) == 0)
    new_hist, new_scale, found_inf = update_packed(
        state.amax_history, state.scale, bufs, plan, policy,
        fp8_max_value=fp8_max_value, update=do,
        scale_min_metric=f"{telemetry_prefix}/scale_min",
        amax_max_metric=f"{telemetry_prefix}/amax_max")
    return Fp8State(amax_history=new_hist, scale=new_scale,
                    step=state.step + 1), found_inf


def update_packed(amax_history: Sequence[jax.Array],
                  scale: Sequence[jax.Array],
                  bufs: Sequence[jax.Array], plan: BucketPlan,
                  policy: Fp8Policy, *,
                  fp8_max_value: Optional[float] = None, update,
                  scale_min_metric: Optional[str] = None,
                  amax_max_metric: Optional[str] = None):
    """THE packed per-bucket transition (one
    ``mt.flat_amax_scale_update`` pass per bucket + the telemetry
    reduce) — shared by :func:`update_state` (gradient-side
    ``Fp8State``, cadence from ``state.step``) and the optimizer's
    weight-scale slots (``FusedOptimizerBase._fp8_slot_update``,
    cadence from the step clock), so the two carriers can never
    drift.  ``update`` is the caller's already-resolved gate.
    Returns ``(new_histories, new_scales, found_inf)``."""
    if len(bufs) != len(plan.buckets):
        raise ValueError(
            f"fp8 state covers {len(plan.buckets)} bucket(s), got "
            f"{len(bufs)} buffer(s)")
    # fp8_max_value is static config (a Python float), never traced
    fmax = (policy.fwd_max() if fp8_max_value is None
            else fp8_max_value)
    new_hist, new_scale, flags = [], [], []
    for bi, buf in enumerate(bufs):
        h, s, f = mt.flat_amax_scale_update(
            buf, plan.segment_sizes(bi), amax_history[bi], scale[bi],
            fp8_max=fmax, margin=policy.margin,
            backoff_factor=policy.backoff_factor, update=update)
        new_hist.append(h)
        new_scale.append(s)
        flags.append(f)
    found_inf = functools.reduce(jnp.maximum, flags)
    # telemetry producers (no-ops without an active tape): a collapsed
    # fp8 scale is THE signature the watchdog's
    # Fp8ScaleCollapseDetector consumes
    if scale_min_metric is not None:
        _tape.emit(scale_min_metric, functools.reduce(
            jnp.minimum, [jnp.min(s) for s in new_scale]))
    if amax_max_metric is not None:
        _tape.emit(amax_max_metric, functools.reduce(
            jnp.maximum, [jnp.max(h[:, 0]) for h in new_hist]),
            reduce="max")
    _tape.emit("fp8/found_inf", found_inf, reduce="max")
    return new_hist, new_scale, found_inf


def update_state_ref(state: Fp8State, tree: Pytree, plan: BucketPlan,
                     policy: Fp8Policy, *,
                     fp8_max_value: Optional[float] = None,
                     skip=None) -> Tuple[Fp8State, jax.Array]:
    """Per-leaf oracle of :func:`update_state`: amax per LEAF via a
    tree walk, the identical transition math per tensor — the
    bit-exactness bar tests hold the packed path to."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != plan.n_leaves:
        raise ValueError("tree does not mirror the plan")
    fmax = (policy.fwd_max() if fp8_max_value is None
            else fp8_max_value)
    do = jnp.equal(state.step % jnp.int32(policy.interval), 0)
    if skip is not None:
        do = jnp.logical_and(do, jnp.asarray(skip, jnp.int32) == 0)
    new_hist, new_scale, flags = [], [], []
    for bi, b in enumerate(plan.buckets):
        amax = jnp.stack([
            jnp.max(jnp.abs(leaves[s.index].astype(jnp.float32)))
            for s in b.leaves])
        h, s, f = mt._amax_scale_math(
            amax, state.amax_history[bi], state.scale[bi], fmax,
            policy.margin, policy.backoff_factor, 2.0 ** 24,
            2.0 ** -24, do)
        new_hist.append(h)
        new_scale.append(s)
        flags.append(f)
    return Fp8State(amax_history=new_hist, scale=new_scale,
                    step=state.step + 1), \
        functools.reduce(jnp.maximum, flags)


def scales_tree(plan: BucketPlan, state: Fp8State) -> Pytree:
    """The per-leaf pytree view of the packed scales (scalar per
    leaf) — the wiring surface for module-level fp8 matmuls
    (``FusedDense(fp8=...)`` weights take their delayed scale from
    here).  Scalar slices fuse into the caller's jit; the hot loop
    never materializes a per-leaf copy of the state."""
    leaves: List[Any] = [None] * plan.n_leaves
    for bi, b in enumerate(plan.buckets):
        for j, s in enumerate(b.leaves):
            leaves[s.index] = state.scale[bi][j]
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def quantize(x: jax.Array, scale, which_or_dtype) -> jax.Array:
    """``x * scale`` saturated into the fp8 format — THE quantize op
    (exactly one convert per call; apexverify spec ``amp.fp8_step``
    pins the program-wide count so casts cannot silently multiply).
    Where the dtype is unavailable the value path saturates the same
    way but stays bf16 (scale bookkeeping unchanged)."""
    if isinstance(which_or_dtype, str):
        dt = fp8_dtype(which_or_dtype)
        fmax = fp8_max(which_or_dtype)
    else:
        dt = which_or_dtype
        fmax = float(jnp.finfo(dt).max)
    y = jnp.clip(x.astype(jnp.float32)
                 * jnp.asarray(scale, jnp.float32), -fmax, fmax)
    return y.astype(dt if dt is not None else jnp.bfloat16)


def dynamic_scale(x: jax.Array, fmax: float) -> jax.Array:
    """Just-in-time (current) scaling for tensors with no delayed
    state — activations and cotangents: ``fmax / amax`` clipped, amax
    zero/non-finite degrading to scale 1 (the overflow then saturates
    and the unscale stays exact)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    ok = (amax > 0) & (jnp.abs(amax) < jnp.float32(jnp.inf))
    return jnp.where(
        ok, jnp.clip(jnp.asarray(fmax, jnp.float32) / amax,
                     2.0 ** -24, 2.0 ** 24), jnp.float32(1.0))
