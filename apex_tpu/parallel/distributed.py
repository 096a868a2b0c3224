"""Data-parallel training utilities (reference: apex/parallel/distributed.py).

The reference DDP registers per-parameter backward hooks, buckets grads,
and overlaps NCCL all-reduce with the rest of backward (SURVEY.md §3.4).
Under SPMD on TPU that whole mechanism disappears: the train step runs
inside shard_map/pjit over the "data" mesh axis, gradients are reduced by
ONE psum that XLA schedules and overlaps itself.  This module keeps the
reference's API shape on top of that reality:

  - ``DistributedDataParallel`` wraps an apply_fn; its
    ``reduce_gradients`` is the explicit psum/pmean (for shard_map-style
    steps).  Bucketing knobs (message_size, delay_allreduce,
    allreduce_trigger_params) are accepted and ignored — XLA's collective
    scheduler owns that decision.
  - ``flat_dist_call`` / ``broadcast_params`` mirror the ctor broadcast.
  - ``Reducer`` is the raw-reduction facade.

Bucket-granular path (flat AMP pipeline): hand ``Reducer`` or
``DistributedDataParallel`` a :class:`BucketPlan` (or a bucketed fused
optimizer) and reduction runs over the plan's flat buckets —
``all_reduce_flat_buffers`` issues ONE psum per dtype bucket instead of
one per leaf, and packed buffer lists stay packed through the
collective so the fused unscale/norm kernel consumes the reduced
buckets directly (amp/flat_pipeline.py wires the whole chain).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.telemetry import _tape

Pytree = Any


def _emit_reduce_telemetry(bufs) -> None:
    """Report collective payload: bytes all-reduced this step (summed
    over calls) and the number of collectives issued.  Shapes/dtypes
    are static, so this is host arithmetic at trace time — nothing is
    added to the compiled program beyond two ring-slot constants.

    Both reduce paths cast to float32 BEFORE the collective (see
    reduce_leaf / _reduce_one_flat_buffer), so the wire payload is
    4 bytes per element regardless of the leaf's storage dtype —
    counting input-dtype bytes under-reported bf16 leaves by half
    until apexcost's static analysis cross-checked this figure
    (tests/test_lint_cost.py pins the agreement)."""
    nbytes = sum(int(b.size) * 4 for b in bufs)
    _tape.emit("ddp/bytes_allreduced", float(nbytes), reduce="sum")
    _tape.emit("ddp/buckets", float(len(bufs)), reduce="sum")


def _in_shard_map(axis_name: str) -> bool:
    """True when called under shard_map/pmap with `axis_name` bound
    (comm.axis_is_bound: NameError-only probe, VERDICT r1 weak #7)."""
    return comm.axis_is_bound(axis_name)


def all_reduce_gradients(grads: Pytree,
                         axis_name: Optional[str] = comm.AXIS_DATA,
                         average: bool = True,
                         gradient_predivide_factor: float = 1.0) -> Pytree:
    """Reduce grads over the data axis (the reference's allreduce_bucket +
    divide-by-world-size, collapsed to one fused collective).

    Explicit contract: ``axis_name=None`` declares a pjit/GSPMD context
    — grads are returned unchanged because XLA already inserted the
    reduction.  With an axis name, the call must be under shard_map/pmap
    with that name bound (probed via the NameError contract above, so
    the same wrapped step works in both execution styles).
    """
    if axis_name is None or not _in_shard_map(axis_name):
        return grads
    world = comm.bound_axis_size(axis_name)
    pre = gradient_predivide_factor
    post = world / pre if average else 1.0 / pre
    _emit_reduce_telemetry(jax.tree_util.tree_leaves(grads))

    @jax.named_scope("apex_ddp/reduce")
    def reduce_leaf(g):
        # same cast discipline as the bucketed path: f32 leaves pay no
        # convert in either direction
        gf = g if g.dtype == jnp.float32 else g.astype(jnp.float32)
        if pre != 1.0:
            gf = gf / pre
        gf = jax.lax.psum(gf, axis_name)
        if post != 1.0:
            gf = gf / post
        return gf if gf.dtype == g.dtype else gf.astype(g.dtype)

    return jax.tree_util.tree_map(reduce_leaf, grads)


@jax.named_scope("apex_ddp/reduce")
def _reduce_one_flat_buffer(b, axis_name, world, pre, post,
                            decompose: str = "psum",
                            out_dtype=None):
    """One bucket's data-parallel sum: f32 accumulation, cast back to
    ``out_dtype`` (default: the buffer's own dtype).

    Cast discipline: an already-f32 bucket pays NO convert in either
    direction — the old unconditional ``astype(f32)``/cast-back pair
    wrapped every f32 bucket (the common case) in two no-op converts
    that sat between the pack and the collective and could block
    fusion.  ``decompose="reduce_scatter"`` lowers the sum as
    psum_scatter + all_gather — bitwise the same result, but the two
    halves are independently schedulable async collectives (the
    scatter's reduction can start as soon as the bucket exists and the
    gather can complete under later compute), the latency-hiding
    scheduler's preferred shape for large buckets (docs/perf.md)."""
    bf = b if b.dtype == jnp.float32 else b.astype(jnp.float32)
    if pre != 1.0:
        bf = bf / pre
    if decompose == "reduce_scatter" and world > 1:
        # scattered flat: a leaf-shaped bucket (BucketPlan) is raveled
        # for the collective and takes its shape back after it
        shape, n = bf.shape, bf.size
        bf = jnp.ravel(bf)
        pad = (-n) % world
        if pad:
            bf = jnp.pad(bf, (0, pad))
        bf = jax.lax.psum_scatter(bf, axis_name, scatter_dimension=0,
                                  tiled=True)
        bf = jax.lax.all_gather(bf, axis_name, axis=0, tiled=True)
        if pad:
            bf = jax.lax.slice(bf, (0,), (n,))
        bf = bf.reshape(shape)
    else:
        bf = jax.lax.psum(bf, axis_name)
    if post != 1.0:
        bf = bf / post
    want = jnp.dtype(out_dtype) if out_dtype is not None \
        else jnp.dtype(b.dtype)
    return bf if bf.dtype == want else bf.astype(want)


def all_reduce_flat_buffers(bufs, axis_name: str = comm.AXIS_DATA,
                            average: bool = True,
                            gradient_predivide_factor: float = 1.0,
                            decompose: str = "psum",
                            always_fp32: bool = False):
    """Bucket-granular all-reduce: ONE collective per flat bucket.

    The flat AMP pipeline's collective stage — gradients arrive packed
    in a BucketPlan layout (a handful of large 1-D buffers instead of
    hundreds of leaves), so DDP-shaped reduction issues one collective
    per bucket.  Same average/predivide semantics as
    ``all_reduce_gradients``; f32 accumulation, results cast back to
    each buffer's dtype — with no convert at all when a bucket is
    already f32.  No-op outside shard_map (pjit/GSPMD already reduced)
    — identical contract to the per-leaf entry point.

    ``decompose="reduce_scatter"`` emits each bucket's sum as
    psum_scatter + all_gather (see :func:`_reduce_one_flat_buffer`).
    ``always_fp32=True`` keeps the REDUCED buffers in f32 instead of
    casting back to the input dtype — the reference's
    ``allreduce_always_fp32`` without the caller pre-casting (which
    paid a second convert on the way in).
    """
    if decompose not in ("psum", "reduce_scatter"):
        raise ValueError(f"unknown decompose {decompose!r}")
    bufs = list(bufs)
    if axis_name is None or not _in_shard_map(axis_name):
        if always_fp32:
            return [b if b.dtype == jnp.float32
                    else b.astype(jnp.float32) for b in bufs]
        return bufs
    world = comm.bound_axis_size(axis_name)
    pre = gradient_predivide_factor
    post = world / pre if average else 1.0 / pre
    _emit_reduce_telemetry(bufs)
    out_dtype = jnp.float32 if always_fp32 else None
    return [_reduce_one_flat_buffer(b, axis_name, world, pre, post,
                                    decompose=decompose,
                                    out_dtype=out_dtype)
            for b in bufs]


def broadcast_params(params: Pytree) -> Pytree:
    """Ctor-time rank-0 broadcast parity.  Under SPMD, "broadcast" means
    "replicate onto the mesh": device_put with a replicated sharding."""
    if not comm.is_initialized():
        return params
    sharding = comm.replicated_sharding()
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), params)


def flat_dist_call(tensors, op: Callable, args=None):
    """Reference-shaped helper (flatten → collective → unflatten).  The
    flatten step is unnecessary under XLA (collectives take pytrees), so
    this simply maps ``op`` over the tensors."""
    if args is not None:
        return [op(t, *args) for t in tensors]
    return [op(t) for t in tensors]


def _resolve_plan(plan):
    """plan= may be a BucketPlan or a bucketed fused optimizer.  An
    optimizer WITHOUT a plan (fuse_buckets=False, or the packer
    declined its tree) is a loud error, not a silent per-leaf
    fallback — the user asked for bucket-granular collectives and must
    learn they are not getting them (FlatGradPipeline raises for the
    same input)."""
    if plan is None:
        return None
    resolved = getattr(plan, "_plan", plan)
    if resolved is None:
        raise ValueError(
            "plan= was given an optimizer without a bucket plan "
            "(fuse_buckets=False or the packer declined its tree) — "
            "bucket-granular reduction needs the bucketed path; omit "
            "plan= for per-leaf reduction")
    return resolved


class Reducer:
    """Raw gradient reducer (reference: apex/parallel/distributed.py::
    Reducer) — explicitly-invoked reduction, no hooks.

    ``plan``: an optional :class:`BucketPlan` (or a bucketed fused
    optimizer, whose plan is borrowed).  With a plan, reduction is
    bucket-granular — pytree grads are packed once and reduced as flat
    buckets (one psum per bucket, the reference's allreduce_bucket
    made literal), and already-packed buffer lists are reduced as-is
    and returned packed, so the flat AMP pipeline keeps grads flat
    straight through the collective."""

    def __init__(self, module_or_grads_list=None,
                 axis_name: str = comm.AXIS_DATA, plan=None):
        self.axis_name = axis_name
        self.plan = _resolve_plan(plan)

    def reduce(self, grads: Pytree, average: bool = True) -> Pytree:
        if self.plan is not None:
            if self.plan.is_packed(grads):
                return all_reduce_flat_buffers(
                    grads, self.axis_name, average=average)
            # no-op contexts (axis unbound / GSPMD) must stay free:
            # don't pay a pack+unpack gradient copy for nothing
            if self.axis_name is None \
                    or not _in_shard_map(self.axis_name):
                return grads
            bufs = all_reduce_flat_buffers(
                self.plan.pack_grads(grads), self.axis_name,
                average=average)
            return self.plan.unpack_grads(bufs)
        return all_reduce_gradients(grads, self.axis_name, average=average)


class DistributedDataParallel:
    """apex.parallel.DistributedDataParallel-shaped wrapper.

    Wraps an ``apply_fn(params, *args) -> out`` (or a flax module's
    ``.apply``).  Forward is a passthrough; ``reduce_gradients`` performs
    the data-parallel mean that the reference performed via backward-hook
    buckets.  Intended use inside a shard_map-decorated train step:

        ddp = DistributedDataParallel(model.apply)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch_shard)
        grads = ddp.reduce_gradients(grads)
    """

    def __init__(self, apply_fn: Callable = None,
                 message_size: int = 10_000_000,
                 delay_allreduce: bool = False,
                 shared_param: Optional[bool] = None,
                 allreduce_trigger_params=None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 axis_name: str = comm.AXIS_DATA,
                 bucket_plan=None,
                 reduce_decompose: str = "psum"):
        # bucketing/overlap knobs accepted for parity; XLA owns scheduling
        del message_size, delay_allreduce, shared_param
        del allreduce_trigger_params, retain_allreduce_buffers
        self.apply_fn = apply_fn
        if reduce_decompose == "auto":
            # measured per-topology preference (tools/autotune.py);
            # absent entry = the design default
            from apex_tpu.ops import _dispatch
            reduce_decompose = _dispatch.pipeline_pref(
                "reduce_decompose", "psum")
        self.reduce_decompose = reduce_decompose
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.axis_name = axis_name
        # bucket_plan: a BucketPlan or bucketed fused optimizer — grads
        # then reduce as flat buckets (one collective per bucket), the
        # honest realization of the knobs deleted above
        self.bucket_plan = _resolve_plan(bucket_plan)

    def __call__(self, *args, **kwargs):
        return self.apply_fn(*args, **kwargs)

    def reduce_gradients(self, grads: Pytree) -> Pytree:
        if self.bucket_plan is not None:
            packed = self.bucket_plan.is_packed(grads)
            if not packed and (self.axis_name is None
                               or not _in_shard_map(self.axis_name)):
                # no-op context: skip the pack+unpack gradient copy
                # (per-leaf path below returns grads untouched too)
                return grads
            bufs = (list(grads) if packed
                    else self.bucket_plan.pack_grads(grads))
            # allreduce_always_fp32 rides the reduction's own f32
            # accumulation (skip the cast-back) instead of pre-casting
            # every bucket — the old pre-cast put a second convert in
            # front of the collective for buckets that were bf16 and a
            # no-op convert for ones already f32
            bufs = all_reduce_flat_buffers(
                bufs, self.axis_name, average=self.gradient_average,
                gradient_predivide_factor=self.gradient_predivide_factor,
                decompose=self.reduce_decompose,
                always_fp32=self.allreduce_always_fp32)
            # packed in -> packed out (the flat pipeline consumes the
            # buckets directly); tree in -> tree out
            return bufs if packed else self.bucket_plan.unpack_grads(bufs)
        if self.allreduce_always_fp32:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
        return all_reduce_gradients(
            grads, self.axis_name, average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor)
