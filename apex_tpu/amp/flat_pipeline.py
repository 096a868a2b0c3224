"""Flat end-to-end AMP gradient pipeline: pack once, fuse everything.

The per-leaf amp surface walks the gradient pytree three to four times
per step — ``unscale_grads``, ``check_finite``, ``clip_grad_norm`` each
sweep every leaf (clip_grad even ravels its own throwaway flat buffer),
and the bucketed optimizer then re-packs the grads inside ``step()``.
That is exactly the per-tensor-launch overhead upstream apex's ``amp_C``
multi-tensor pipeline exists to kill (SURVEY.md §2.3).

This module makes gradients live FLAT from loss to update:

    scaled_value_and_grad          (grads w.r.t. model params)
        └─ pack_grads              ONE concatenate per dtype bucket
            └─ all-reduce          one psum per BUCKET, not per leaf
                └─ flat_unscale_norm   unscale + non-finite + Σg² in
                                       ONE HBM read per bucket
                    └─ tiny combine    global norm, found_inf, clip_coef
                        └─ optimizer.step(FlatGrads)
                                       clip folds into the flat kernels'
                                       grad scaling; grads never unpack

The per-leaf path (amp.scaler + contrib.clip_grad) stays as the oracle
and the fallback for trees the packer declines.

Two schedule refinements ride the same pipeline (ISSUE 10):

* **Interleaved collectives** (``interleave=True`` + a chunked plan):
  each bucket's data-parallel reduce is emitted INSIDE the backward by
  a custom-vjp seam wrapped around that bucket's param leaves, so the
  collective's dependency cone is exactly its own leaves' cotangents —
  never the whole backward.  With buckets chunked
  (``max_bucket_bytes``), bucket k's psum is schedulable while bucket
  k-1's backward compute still runs; XLA's latency-hiding scheduler
  (platform.enable_latency_hiding_scheduler) turns that freedom into
  hidden collective time (docs/perf.md "Overlap schedule").
* **Flat accumulation** (``accumulate()``/``finalize()`` or
  ``microbatches=N``): microbatch gradients add into persistent f32
  accumulator buckets via ONE fused read-modify-write per bucket
  (ops.multi_tensor.flat_accumulate, donated/aliased accumulators),
  found_inf latching across microbatches; the final
  unscale+clip+reduce rides the existing per-bucket kernels, so the
  accumulation loop never materializes a per-leaf gradient tree.
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.amp.scaler import (LossScaleState, scale_loss,
                                 split_microbatch_args)
from apex_tpu.multi_tensor_apply.packer import BucketPlan, cached_plan
from apex_tpu.ops import _dispatch
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.telemetry import _tape

Pytree = Any


class FlatGrads(NamedTuple):
    """The flat pipeline's gradient bundle (a pytree — jit-safe).

    ``bufs``: unscaled per-bucket flat gradient buffers in the plan's
    layout.  ``grad_norm``: PRE-clip global L2 norm of the unscaled
    gradients (f32; NaN when non-finite — see found_inf).  ``found_inf``:
    i32 overflow flag (any non-finite unscaled element).  ``clip_coef``:
    f32 global-norm clip coefficient in (0, 1], exactly 1.0 when no
    clipping applies; fold it into the optimizer step, never into the
    buffers (``FusedOptimizerBase.step`` does this for you).
    """
    bufs: List[jax.Array]
    grad_norm: jax.Array
    found_inf: jax.Array
    clip_coef: jax.Array


class GradAccum(NamedTuple):
    """Persistent microbatch gradient-accumulation state (a pytree).

    ``bufs``: per-bucket f32 accumulator buffers in the plan's layout
    (SCALED gradients accumulate; unscale happens once at finalize).
    ``found_inf``: i32 latch — set by ANY microbatch whose gradients
    (or their running sum) went non-finite, so one bad microbatch
    skips the whole committed step, branch-free.  ``count``: i32
    number of microbatches accumulated (finalize's averaging divisor).

    Donate the buffers to the jitted accumulation step
    (``flat_accumulate`` aliases its accumulator input to its output)
    — the add is then in place, one HBM read-modify-write per bucket.
    """
    bufs: List[jax.Array]
    found_inf: jax.Array
    count: jax.Array

    @staticmethod
    def zeros(plan: BucketPlan) -> "GradAccum":
        return GradAccum(
            bufs=[jnp.zeros(b.shape, jnp.float32)
                  for b in plan.buckets],
            found_inf=jnp.int32(0), count=jnp.int32(0))


def _scaler_state(state) -> LossScaleState:
    """Accept a LossScaleState or anything carrying one (AmpState)."""
    return getattr(state, "scaler", state)


class FlatGradPipeline:
    """Pack-once gradient pipeline over a :class:`BucketPlan`.

    Construct from a bucketed fused optimizer (reuses its plan — the
    buffers then feed ``optimizer.step`` with ZERO re-packing) or from
    a params/grads pytree (a standalone cached plan is built).

    ``max_grad_norm > 0`` enables fused global-norm clipping: the norm
    falls out of the unscale kernel for free and the clip coefficient
    rides the optimizer kernels' existing grad scaling.  ``axis_name``
    enables bucket-granular data-parallel all-reduce (one collective
    per flat bucket) between pack and unscale, mirroring the reference
    DDP's reduce-then-unscale ordering.

    ``interleave=True`` moves each bucket's reduce INTO the backward
    (custom-vjp seam per bucket): the collective depends only on its
    own leaves' cotangents, so with a chunked plan
    (``max_bucket_bytes``, or the optimizer's own) the scheduler can
    hide bucket k's collective under bucket k-1's backward compute.
    Numerically identical to the trailing schedule (same f32 psum per
    bucket, same ordering of adds); a no-op when ``axis_name`` is None
    or unbound.  ``reduce_decompose="reduce_scatter"`` lowers each
    bucket's sum as psum_scatter + all_gather (async-friendlier halves
    — see parallel.distributed).
    """

    def __init__(self, optimizer=None, plan: Optional[BucketPlan] = None,
                 params: Optional[Pytree] = None,
                 max_grad_norm: float = 0.0,
                 axis_name: Optional[str] = None,
                 average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 eps: float = 1e-6,
                 defer_plan: bool = False,
                 interleave: bool = False,
                 reduce_decompose: str = "psum",
                 max_bucket_bytes=None,
                 fp8=None):
        if reduce_decompose == "auto":
            # measured per-topology preference (tools/autotune.py);
            # absent entry = the design default
            reduce_decompose = _dispatch.pipeline_pref(
                "reduce_decompose", "psum")
        if max_bucket_bytes == "auto":
            supplied = plan if plan is not None \
                else getattr(optimizer, "_plan", None)
            if supplied is not None:
                # a supplied plan owns its chunking: "auto" asks the
                # measured table only when THIS pipeline derives the
                # plan (chunk at the source, e.g. FusedAdam(...,
                # max_bucket_bytes=...), to steer a shared plan)
                max_bucket_bytes = getattr(supplied,
                                           "max_bucket_bytes", None)
            else:
                max_bucket_bytes = _dispatch.pipeline_pref(
                    "max_bucket_bytes", None)
        if plan is None and optimizer is not None:
            plan = getattr(optimizer, "_plan", None)
            if plan is None:
                raise ValueError(
                    "optimizer has no bucket plan (fuse_buckets=False or "
                    "the packer declined its tree) — the flat pipeline "
                    "needs the bucketed path; use the per-leaf amp "
                    "surface instead")
        if plan is not None and max_bucket_bytes is not None \
                and getattr(plan, "max_bucket_bytes",
                            None) != max_bucket_bytes:
            # a supplied plan (optimizer=/plan=) wins over any later
            # derivation, so a mismatching chunking request would be
            # SILENTLY ignored — and with interleave=True the overlap
            # schedule would silently degrade to the plan's (possibly
            # monolithic, trailing-equivalent) layout
            raise ValueError(
                "max_bucket_bytes conflicts with the supplied plan "
                f"(built with max_bucket_bytes="
                f"{getattr(plan, 'max_bucket_bytes', None)}) — chunk "
                "at the source instead, e.g. FusedAdam(..., "
                "max_bucket_bytes=N), or omit it here")
        if plan is None and params is not None:
            plan = cached_plan(params, max_bucket_bytes=max_bucket_bytes)
        if plan is None and not defer_plan:
            raise ValueError("need one of optimizer=, plan= or params= "
                             "(or defer_plan=True to derive the plan "
                             "from the first gradient tree packed)")
        if reduce_decompose not in ("psum", "reduce_scatter"):
            raise ValueError(
                f"unknown reduce_decompose {reduce_decompose!r}")
        self.plan = plan
        self.optimizer = optimizer
        self.max_grad_norm = float(max_grad_norm)
        self.axis_name = axis_name
        self.average = average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.eps = float(eps)
        self.interleave = bool(interleave)
        self.reduce_decompose = reduce_decompose
        self.max_bucket_bytes = max_bucket_bytes
        # fp8 delayed scaling for the GRADIENT side: e5m2 per-tensor
        # scale state packed in this plan's layout (docs/amp.md "fp8
        # training") — fp8=True resolves the autotuned policy
        if fp8 is True:
            from apex_tpu.amp.fp8 import tuned_policy
            fp8 = tuned_policy()
        self.fp8 = fp8
        self._seams: dict = {}

    # ---- stages ----------------------------------------------------------
    def pack(self, grads: Pytree) -> List[jax.Array]:
        """Pytree -> per-bucket flat buffers (the ONE gradient pack);
        already-packed input passes through untouched."""
        if self.plan is None:   # defer_plan: derive from the first tree
            self.plan = cached_plan(
                grads, max_bucket_bytes=self.max_bucket_bytes)
            if self.plan is None:
                raise ValueError(
                    "flat pipeline: the packer declined this gradient "
                    "tree (non-float or multi-device leaves) — use the "
                    "per-leaf amp surface")
        if self.plan.is_packed(grads):
            return list(grads)
        return self.plan.pack_grads(grads)

    def reduce(self, bufs: List[jax.Array]) -> List[jax.Array]:
        """Bucket-granular data-parallel all-reduce (no-op without
        ``axis_name`` or outside shard_map/pmap)."""
        if self.axis_name is None:
            return bufs
        from apex_tpu.parallel.distributed import all_reduce_flat_buffers
        return all_reduce_flat_buffers(
            bufs, self.axis_name, average=self.average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            decompose=self.reduce_decompose)

    # ---- interleaved collectives (reduce-in-backward seam) ---------------
    def _bucket_seam(self, bucket_index: int):
        """Custom-vjp identity over one bucket's param leaves whose
        backward packs that bucket's cotangents and reduces them over
        the data axis RIGHT THERE — the collective's dependency cone is
        exactly this bucket's cotangent subgraph, never the rest of the
        backward, so the lowered schedule is free to overlap it with
        the remaining bucket's compute.  The slices it returns fold
        with the pipeline's later re-pack (slice-of-concat /
        concat-of-slices cancel in XLA's simplifier), so the seam adds
        no extra gradient copy."""
        b = self.plan.buckets[bucket_index]
        axis = self.axis_name
        avg, pre = self.average, self.gradient_predivide_factor
        dec = self.reduce_decompose

        @jax.custom_vjp
        def seam(leaves):
            return leaves

        def fwd(leaves):
            return leaves, None

        def bwd(_, cts):
            from apex_tpu.parallel.distributed import \
                all_reduce_flat_buffers
            parts = [jnp.ravel(c) for c in cts]
            buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            buf = all_reduce_flat_buffers(
                [buf], axis, average=avg,
                gradient_predivide_factor=pre, decompose=dec)[0]
            return (tuple(
                jax.lax.slice(buf, (s.offset,),
                              (s.offset + s.size,)).reshape(s.shape)
                for s in b.leaves),)

        seam.defvjp(fwd, bwd)
        return seam

    def _interleave_params(self, params: Pytree) -> Pytree:
        """Thread every bucket's leaves through its reduce-in-backward
        seam (forward: identity)."""
        if self.plan is None:
            self.plan = cached_plan(
                params, max_bucket_bytes=self.max_bucket_bytes)
            if self.plan is None:
                raise ValueError(
                    "interleave: the packer declined the params tree")
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if len(leaves) != self.plan.n_leaves:
            raise ValueError(
                "interleave: params tree does not match the bucket plan")
        for bi, b in enumerate(self.plan.buckets):
            seam = self._seams.get(bi)
            if seam is None:
                seam = self._seams[bi] = self._bucket_seam(bi)
            outs = seam(tuple(leaves[s.index] for s in b.leaves))
            for s, o in zip(b.leaves, outs):
                leaves[s.index] = o
        return jax.tree_util.tree_unflatten(treedef, leaves)

    @jax.named_scope("apex_amp/unscale")
    def unscale_and_norm(self, bufs: List[jax.Array],
                         state=None, inv_scale=None) -> FlatGrads:
        """One ``flat_unscale_norm`` kernel per bucket + tiny combine.

        Pass either a scaler ``state`` (LossScaleState/AmpState) or an
        explicit ``inv_scale``; omit both for already-unscaled grads
        (inv_scale=1 — the kernel still yields norm + found_inf)."""
        if inv_scale is None:
            inv_scale = (1.0 / _scaler_state(state).loss_scale
                         if state is not None else jnp.float32(1.0))
        outs, norm_sqs, flags = [], [], []
        for buf in bufs:
            o, nsq, flag = mt.flat_unscale_norm(buf, inv_scale)
            outs.append(o)
            norm_sqs.append(nsq)
            flags.append(flag)
        found_inf = functools.reduce(jnp.maximum, flags)
        norm = jnp.sqrt(sum(norm_sqs, jnp.float32(0.0)))
        maxn = jnp.asarray(self.max_grad_norm, jnp.float32)
        clip = jnp.where((maxn > 0) & (norm > maxn),
                         maxn / (norm + self.eps), jnp.float32(1.0))
        # overflow (inf/NaN norm): the step is skipped via found_inf
        # regardless, so pin clip_coef to the neutral 1.0 — deterministic
        # whether the norm overflowed to inf (clip would be 0) or NaN
        # (comparison False); no 0-or-NaN coefficient ever leaks out
        clip = jnp.where(found_inf > 0, jnp.float32(1.0), clip)
        # telemetry producers (trace-time no-ops without an active
        # tape): the signals below already exist on device — reporting
        # them costs nothing and syncs nothing
        _tape.emit("amp/grad_norm", norm)
        _tape.emit("amp/found_inf", found_inf, reduce="max")
        _tape.emit("amp/clip_coef", clip)
        return FlatGrads(bufs=outs, grad_norm=norm,
                         found_inf=found_inf, clip_coef=clip)

    # ---- fp8 delayed scaling (gradient side) -----------------------------
    def fp8_init(self):
        """Fresh packed :class:`~apex_tpu.amp.fp8.Fp8State` over this
        plan — per-tensor amax history + e5m2 scales for the gradient
        buffers.  Thread it through the jitted step next to the loss
        scaler and feed the step's flag to the optimizer's
        ``found_inf=``."""
        from apex_tpu.amp import fp8 as _fp8
        if self.fp8 is None:
            raise ValueError("pipeline was built without fp8= policy")
        if self.plan is None:
            raise ValueError("fp8_init needs a resolved plan "
                             "(construct with optimizer=/plan=/params=)")
        return _fp8.init_state(self.plan, self.fp8)

    def fp8_update(self, fp8_state, flat: FlatGrads):
        """Roll the unscaled gradient buffers' per-tensor amax into
        the delayed-scaling state (ONE flat pass per bucket) and
        latch any fp8 overflow into the bundle's ``found_inf`` — a
        poisoned scale state skips the step and holds the step clock
        exactly like a loss-scale overflow.  A step already skipped
        (``flat.found_inf``) holds the fp8 history too — garbage amax
        must never enter the window — while an overflowed tensor's
        scale still backs off (the loss scaler's own skip-and-back-off
        shape; see ``amp.fp8.update_state``).  Returns
        ``(flat', new_state)``.
        """
        from apex_tpu.amp import fp8 as _fp8
        if self.fp8 is None:
            raise ValueError("pipeline was built without fp8= policy")
        new_state, f8_inf = _fp8.update_state(
            fp8_state, flat.bufs, self.plan, self.fp8,
            fp8_max_value=self.fp8.bwd_max(), skip=flat.found_inf)
        return (flat._replace(
            found_inf=jnp.maximum(flat.found_inf, f8_inf)), new_state)

    # ---- microbatch accumulation -----------------------------------------
    def init_accum(self) -> GradAccum:
        """Fresh zeroed accumulator state in the plan's layout."""
        if self.plan is None:
            raise ValueError("init_accum needs a resolved plan "
                             "(construct with optimizer=/plan=/params=)")
        return GradAccum.zeros(self.plan)

    def accumulate(self, acc: GradAccum, grads: Pytree) -> GradAccum:
        """Add one microbatch's (still-scaled) gradients into the
        accumulator: pack if needed (already-packed buffers pass
        through), then ONE fused read-modify-write per bucket.  The
        overflow flag latches — a single bad microbatch marks the
        whole accumulation window."""
        bufs = self.pack(grads)
        new, flags = [], [acc.found_inf]
        for a, g in zip(acc.bufs, bufs):
            o, f = mt.flat_accumulate(a, g)
            new.append(o)
            flags.append(f)
        return GradAccum(bufs=new,
                         found_inf=functools.reduce(jnp.maximum, flags),
                         count=acc.count + 1)

    def finalize(self, acc: GradAccum, state=None, inv_scale=None,
                 average: bool = True, fp8_state=None):
        """Accumulator -> FlatGrads: ONE data-parallel reduce per
        bucket (grad accumulation reduces once per committed step, not
        per microbatch), then the fused unscale+norm+clip epilogue
        with the loss scale and the microbatch count folded into a
        single ``inv_scale`` (``average=True`` divides by ``count`` —
        the mean-over-global-batch convention).  The latched
        ``found_inf`` ORs into the epilogue's own detection.

        ``fp8_state``: delayed-scaling gradient state — its amax
        update rides the finalized (unscaled) buffers and the return
        becomes ``(flat, new_fp8_state)``."""
        bufs = self.reduce(acc.bufs)
        if inv_scale is None:
            inv_scale = (1.0 / _scaler_state(state).loss_scale
                         if state is not None else jnp.float32(1.0))
        inv_scale = jnp.asarray(inv_scale, jnp.float32)
        if average:
            inv_scale = inv_scale / jnp.maximum(
                acc.count, 1).astype(jnp.float32)
        flat = self.unscale_and_norm(bufs, inv_scale=inv_scale)
        flat = flat._replace(
            found_inf=jnp.maximum(flat.found_inf, acc.found_inf))
        if fp8_state is not None:
            return self.fp8_update(fp8_state, flat)
        return flat

    def reset_accum(self, acc: GradAccum) -> GradAccum:
        """Zeroed accumulator for the next step, reusing the buffer
        shapes (trace-safe; under donation XLA reuses the storage)."""
        return GradAccum(bufs=[jnp.zeros_like(b) for b in acc.bufs],
                         found_inf=jnp.int32(0), count=jnp.int32(0))

    # ---- end-to-end ------------------------------------------------------
    def scaled_value_and_grad(self, loss_fn, state, *args,
                              has_aux: bool = False,
                              microbatches: int = 1,
                              fp8_state=None, **kwargs):
        """value_and_grad of the LOSS-SCALED objective, gradients flat.

        The flat analog of ``amp.scaled_value_and_grad``: returns
        ``((loss, aux?), FlatGrads)`` where the FlatGrads buffers are
        unscaled, reduced (when ``axis_name``), and carry the global
        norm, overflow flag and clip coefficient — ready for
        ``optimizer.step(flat_grads)``.

        With ``interleave=True`` each bucket's reduce runs inside the
        backward (see class docstring) and the trailing reduce stage
        is skipped.

        ``microbatches=N`` (N > 1) splits every batch argument
        (``args[1:]``) along its leading axis into N microbatches and
        accumulates gradients FLAT across a ``lax.scan``: one pack +
        one fused ``flat_accumulate`` per bucket per microbatch, zero
        per-leaf unpacking, found_inf latched across microbatches,
        data-parallel reduce deferred to the single finalize.  The
        returned loss is the mean over microbatches (== the mean over
        the full batch for a mean-over-examples loss); with
        ``has_aux`` the aux comes back stacked along a leading
        microbatch axis.

        ``fp8_state``: packed delayed-scaling gradient state
        (``fp8_init()``) — the amax/scale update rides the unscaled
        buffers (one flat pass per bucket) and the return grows a
        trailing ``new_fp8_state``, with any fp8 overflow latched
        into ``flat.found_inf``.
        """
        sstate = _scaler_state(state)
        if microbatches > 1:
            return self._microbatched(loss_fn, sstate, args,
                                      has_aux, int(microbatches),
                                      kwargs, fp8_state)
        interleaved = self.interleave and self.axis_name is not None

        def scaled_fn(*a, **kw):
            if interleaved:
                a = (self._interleave_params(a[0]),) + tuple(a[1:])
            out = loss_fn(*a, **kw)
            if has_aux:
                loss, aux = out
                return scale_loss(loss, sstate), aux
            return scale_loss(out, sstate)

        if has_aux:
            (scaled, aux), grads = jax.value_and_grad(
                scaled_fn, has_aux=True)(*args, **kwargs)
        else:
            scaled, grads = jax.value_and_grad(scaled_fn)(*args, **kwargs)
            aux = None
        bufs = self.pack(grads)
        if not interleaved:      # seam already reduced in the backward
            bufs = self.reduce(bufs)
        flat = self.unscale_and_norm(bufs, sstate)
        loss = scaled / sstate.loss_scale
        _tape.emit("amp/loss_scale", sstate.loss_scale)
        _tape.emit("loss", loss)
        if fp8_state is not None:
            flat, fp8_state = self.fp8_update(fp8_state, flat)
            if has_aux:
                return (loss, aux), flat, fp8_state
            return loss, flat, fp8_state
        if has_aux:
            return (loss, aux), flat
        return loss, flat

    def _microbatched(self, loss_fn, sstate, args, has_aux, n, kwargs,
                      fp8_state=None):
        """The ``microbatches=N`` body: scan over leading-axis splits,
        accumulating packed gradients (never a per-leaf tree)."""
        params, xs = split_microbatch_args(args, n)
        if self.plan is None:
            # resolve the plan from the params tree (same structure,
            # shapes and dtypes as the gradients) so init_accum can
            # size the buffers before the first backward
            self.plan = cached_plan(
                params, max_bucket_bytes=self.max_bucket_bytes)
            if self.plan is None:
                raise ValueError(
                    "microbatches: the packer declined the params tree")

        def scaled_fn(p, *b):
            out = loss_fn(p, *b, **kwargs)
            if has_aux:
                loss, aux = out
                return scale_loss(loss, sstate), aux
            return scale_loss(out, sstate), None

        def body(carry, micro):
            acc, scaled_sum = carry
            (scaled, aux), grads = jax.value_and_grad(
                scaled_fn, has_aux=True)(params, *micro)
            acc = self.accumulate(acc, grads)
            return (acc, scaled_sum + scaled), aux

        (acc, scaled_sum), auxes = jax.lax.scan(
            body, (self.init_accum(), jnp.float32(0.0)), xs)
        out = self.finalize(acc, sstate, average=True,
                            fp8_state=fp8_state)
        flat, new_fp8 = out if fp8_state is not None else (out, None)
        loss = scaled_sum / (jnp.float32(n) * sstate.loss_scale)
        _tape.emit("amp/loss_scale", sstate.loss_scale)
        _tape.emit("loss", loss)
        if fp8_state is not None:
            if has_aux:
                return (loss, auxes), flat, new_fp8
            return loss, flat, new_fp8
        if has_aux:
            return (loss, auxes), flat
        return loss, flat

    def step(self, flat: FlatGrads, grad_scale=1.0) -> Pytree:
        """``optimizer.step`` on the packed buffers — found_inf drives
        the branch-free skip, clip_coef folds into the kernels."""
        if self.optimizer is None:
            raise ValueError("pipeline was built without an optimizer")
        return self.optimizer.step(flat, grad_scale=grad_scale)

    def grads_tree(self, flat: FlatGrads) -> Pytree:
        """Unpack the buffers to a pytree (inspection/tests only — the
        hot loop never needs this)."""
        return self.plan.unpack_grads(flat.bufs)
