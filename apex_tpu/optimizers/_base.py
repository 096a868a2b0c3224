"""Shared machinery for the fused optimizer facades.

The reference optimizers subclass torch.optim.Optimizer and mutate params
in place via one multi-tensor launch (e.g. apex/optimizers/fused_adam.py,
SURVEY.md §3.3).  The JAX facade keeps that class shape — construct with a
params pytree, call ``step(grads)`` — but is a thin stateful wrapper over
a pure, jitted ``(params, opt_state, grads, scalars) -> (params,
opt_state)`` function, so the same math can also be embedded directly in a
user's jitted train step via the ``functional_step`` attribute.

Bucketed flat path (default, ``fuse_buckets=True``): at construction a
one-time :class:`~apex_tpu.multi_tensor_apply.packer.BucketPlan`
concatenates dtype-homogeneous leaves into flat HBM buffers, and the
jitted step runs ONE sweep per bucket and phase of the update
(apex_tpu.ops.multi_tensor's ``flat_adam`` / ``flat_sgd`` / ... ``jnp``
math, which XLA fuses with the overflow skip and the model-dtype copy
of the masters: every buffer is read once and written once per phase)
— the TPU realization of the reference's ``multi_tensor_apply`` +
``amp_C`` design.  Params, masters and optimizer state stay PACKED
between steps; the per-leaf pytree view is rebuilt lazily (one compiled
unpack program) only for ``state_dict()``, ``load_state_dict()`` and the
``params``/``masters`` properties, and the checkpoint layout is
unchanged — old per-leaf checkpoints load into bucketed optimizers and
vice versa.  ``fuse_buckets=False`` (or any
tree the packer declines: non-float leaves, multi-device shardings)
falls back to the traced per-leaf update.

Ownership on the bucketed path (docs/optimizers.md): the optimizer
COPIES IN whatever it is handed (constructor, ``params`` / ``masters``
setters, ``load_state_dict``, ``load_packed_snapshot``: the pack, or a
copy where the pack would be the caller's own array), the step DONATES
the packed parameters, masters and state and updates them in place, and
everything handed OUT (``step()``'s result, ``params``, ``masters``,
``state_dict()``, ``packed_snapshot()``) is a fresh array the caller
may keep across any number of steps.

Master weights: when params are bf16/fp16 and ``master_weights=True`` the
facade keeps f32 masters, steps those, and writes back model-dtype params
(reference O2 contract, apex/amp/_process_optimizer.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply.packer import BucketPlan
from apex_tpu.telemetry import _tape
from apex_tpu.telemetry.retrace import mark_step, phased
from apex_tpu.telemetry.spans import span

Pytree = Any
tree_map = jax.tree_util.tree_map

# in-jit "move to device memory" marker
_DEVICE_MEMORY = jax.memory.Space.Device


def _host_sharding(x: jax.Array):
    """The array's own sharding, re-homed to pinned host memory (the
    TPU host-offload target; the CPU backend exposes it too)."""
    return x.sharding.with_memory_kind("pinned_host")


def _device_sharding(x: jax.Array):
    return x.sharding.with_memory_kind("device")


def place_on_host(tree: Pytree) -> Pytree:
    """Eagerly move every array leaf to host memory, preserving its
    device/mesh sharding."""
    return tree_map(
        lambda x: jax.device_put(x, _host_sharding(x))
        if isinstance(x, jax.Array) else x, tree)


def place_on_device(tree: Pytree) -> Pytree:
    return tree_map(
        lambda x: jax.device_put(x, _device_sharding(x))
        if isinstance(x, jax.Array) else x, tree)


def _replica_mesh(tree: Pytree):
    """The mesh ``tree``'s arrays are replicated over, or None for the
    single-device case (decided on the first array leaf: the gradients
    of one step share a placement)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or len(sharding.device_set) <= 1:
            return None
        mesh = getattr(sharding, "mesh", None)
        if mesh is None:
            from apex_tpu import comm
            mesh = comm.mesh()
        return mesh
    return None


def _own_pack(bufs, tree: Pytree) -> List[jax.Array]:
    """``bufs`` as packed from ``tree``, none of them one of its
    leaves: a one-leaf bucket of a flat leaf that already has the
    target dtype packs to the leaf ITSELF (``BucketPlan.pack``), and
    the step donates what the optimizer holds — that one is copied."""
    theirs = {id(x) for x in jax.tree_util.tree_leaves(tree)}
    return [jnp.copy(b) if id(b) in theirs else b for b in bufs]


def _owned(buf) -> jax.Array:
    """``buf`` as a device buffer that nothing outside the optimizer
    holds (the step donates it): a jax array is copied, host data is
    put on the device."""
    return _device_copy(buf) if isinstance(buf, jax.Array) \
        else jnp.asarray(buf)


def _device_copy(buf: jax.Array) -> jax.Array:
    """Async copy of one flat buffer within its own memory space
    (dispatch returns immediately; ``buf.copy()`` would land a
    host-offloaded buffer in device memory).  The bucket-native
    checkpoint path routes every copy through this seam so tests can
    assert structurally that a packed snapshot is exactly one copy per
    buffer and nothing else."""
    return jax.device_put(buf, buf.sharding, may_alias=False)


def unzip_tree(like: Pytree, tree_of_tuples: Pytree, n: int):
    """pytree-of-n-tuples -> n-tuple of pytrees (robust to tuples INSIDE
    the params pytree, unlike is_leaf=isinstance(tuple))."""
    outer = jax.tree_util.tree_structure(like)
    inner = jax.tree_util.tree_structure(tuple(range(n)))
    return jax.tree_util.tree_transpose(outer, inner, tree_of_tuples)


def _is_low_precision(tree) -> bool:
    return any(l.dtype in (jnp.bfloat16, jnp.float16)
               for l in jax.tree_util.tree_leaves(tree)
               if jnp.issubdtype(l.dtype, jnp.floating))


def _select(keep, new_tree, old_tree):
    """Branch-free elementwise keep?new:old over matching pytrees (the
    amp found_inf skip — mirrors amp.scaler.conditional_step, never a
    host sync)."""
    return tree_map(lambda a, b: jnp.where(keep, a, b), new_tree, old_tree)


def _keep_flag(found_inf):
    """``found_inf == 0`` as the step's traced ``keep`` scalar (None
    without a flag: then no select is traced at all), reporting the
    skip.  The telemetry emission lands only when the body is traced
    inside an instrumented jit (functional_step, or a train step
    embedding ``_full_step_impl``); the stateful ``step()`` facade's
    internal jit cannot report into an outer ring — the tape correctly
    drops its tracers (telemetry._tape docstring)."""
    if found_inf is None:
        return None
    _tape.emit("optim/skipped", jnp.asarray(found_inf) > 0,
               reduce="max")
    return jnp.asarray(found_inf) == 0


@jax.named_scope("apex_optim/skip_select")
def _skip_on_overflow(found_inf, new_work, old_work, new_state,
                      old_state):
    """The branch-free found_inf skip of the per-leaf step bodies (all
    ``jnp``: XLA fuses the selects into the update): keep the old
    values when the flag is set, and report the skip.  The bucketed
    path hands ``_keep_flag`` to its sweeps instead."""
    keep = _keep_flag(found_inf)
    return (_select(keep, new_work, old_work),
            _select(keep, new_state, old_state))


def _fold_clip(grad_scale, clip_coef):
    """Fold a global-norm clip coefficient into the gradient scale.

    Every flat_* update (and the per-leaf math) multiplies grads by
    ``1/grad_scale``; an effective scale of ``grad_scale/clip_coef``
    therefore multiplies by ``clip_coef/grad_scale`` — clipping rides
    the scaling the updates already do, with no extra gradient pass or
    copy.  LAMB's global-grad-norm prologue composes correctly: it sees
    the norm of the gradients AS CLIPPED, which is what its own
    max_grad_norm logic should be judging."""
    gs = jnp.asarray(grad_scale, jnp.float32)
    if clip_coef is None:
        return gs
    return gs / jnp.asarray(clip_coef, jnp.float32)


# fp8 delayed-scaling state carried as packed optimizer slots (see
# enable_fp8): updated by the step itself from the post-update work
# buffers, donated/offloaded/checkpointed like every other slot, and
# excluded from the per-bucket optimizer math.
_FP8_SLOTS = ("fp8_amax_history", "fp8_scale")


class FusedOptimizerBase:
    """Subclasses set ``defaults`` and implement ``_step_math`` (per-leaf
    oracle path) plus ``_flat_bucket_step`` (bucketed flat path)."""

    # whether the bucketed step donates the packed WORK buffers (the
    # masters, or the parameters where there are none) beside the state
    # and the model-dtype copy: FusedLAMB says no, and why
    _donate_work = True

    # the plan, the packed parameters, masters and state: once a process
    @phased("apex/optim/init")
    def __init__(self, params: Pytree, master_weights: Optional[bool] = None,
                 masters: Optional[Pytree] = None,
                 offload_state: bool = False,
                 fuse_buckets: bool = True,
                 max_bucket_bytes: Optional[int] = None, **hypers):
        self.hypers: Dict[str, Any] = dict(self.defaults)
        unknown = set(hypers) - set(self.hypers)
        if unknown:
            raise TypeError(f"unexpected arguments {sorted(unknown)}")
        self.hypers.update(hypers)
        if masters is not None:
            # externally-sourced masters (amp.initialize's copies made
            # from the ORIGINAL f32 init — upcasting the rounded half
            # params here would lose the low bits, apex O2 contract)
            if master_weights is False:
                raise ValueError(
                    "masters= provided together with "
                    "master_weights=False — contradictory")
            if not _is_low_precision(params):
                raise ValueError(
                    "masters= provided but params are not low-precision"
                    " — masters only apply to half-precision params")
            if (jax.tree_util.tree_structure(masters)
                    != jax.tree_util.tree_structure(params)):
                raise ValueError(
                    "masters pytree structure does not match params")
            master_weights = True
        if master_weights is None:
            master_weights = _is_low_precision(params)
        self.master_weights = master_weights and _is_low_precision(params)
        if not self.master_weights:
            masters = None
        else:
            masters = tree_map(
                lambda x: x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                masters if masters is not None else params)
        work = masters if masters is not None else params

        # ---- bucket plan (tentpole): one-time packing layout --------------
        # max_bucket_bytes: optional chunking cap — multiple buckets
        # per dtype group so the DDP collectives become per-chunk and
        # schedulable under the remaining backward (docs/perf.md
        # "Overlap schedule"); None keeps the maximal-fusion default
        self._plan = (BucketPlan.from_tree(
            work, params if masters is not None else None,
            max_bucket_bytes=max_bucket_bytes)
            if fuse_buckets else None)
        self.fuse_buckets = self._plan is not None
        self._params_tree = None
        self._masters_tree = None
        self._params_cache = None
        self._masters_cache = None
        if self._plan is not None:
            self._adopt(params, masters)
            self.opt_state = self.init_state_packed(self._plan, work)
            self._full_step_impl = self._full_step_flat
        else:
            self._params_tree = params
            self._masters_tree = masters
            self.opt_state = self.init_state(work)
            self._full_step_impl = self._full_step
        self.step_count = jnp.int32(0)
        self._mesh_steps: Dict[Any, Any] = {}
        # Host-offloaded optimizer state (beyond-reference; the HBM
        # relief the reference gets from ZeRO sharding alone).  On TPU
        # the step is ONE program: state transfers in from pinned host,
        # math runs on device, out_shardings land the new state back on
        # host (XLA overlaps the DMAs with compute).  Bucketed state
        # offloads as WHOLE flat buffers — a handful of large DMAs
        # instead of one per leaf.  Elsewhere (CPU CI) the in-jit
        # placement custom call doesn't exist, so step() moves the
        # state eagerly around a plain device step.
        self.offload_state = offload_state
        self._fused_offload = False
        if offload_state:
            from apex_tpu.ops._dispatch import on_tpu
            self.opt_state = place_on_host(self.opt_state)
            self._fused_offload = on_tpu()
        self._jit_step = self._build_jit_step()

    def _build_jit_step(self):
        """The step program for the current plan and state layout."""
        if self._fused_offload:
            # no donation: the state crosses memory kinds
            # (pinned_host in, device math, pinned_host out) and
            # donating across spaces is not aliasable anyway
            return jax.jit(  # apexlint: disable=APX401
                self._full_step_offload,
                out_shardings=(None, None,
                               tree_map(_host_sharding, self.opt_state)))
        return jax.jit(self._full_step_impl, **self._donation)

    @property
    def _donation(self):
        """``jax.jit``'s arguments that say what the step program
        updates in place: the state and, on the bucketed path, the
        packed parameter and master buffers, which nothing but the
        optimizer holds (``_adopt``).  ``keep_unused``: with masters
        the program never reads the packed model-dtype parameters, and
        an argument jit prunes cannot give its buffer to the output
        that replaces it.  The per-leaf path's trees are the caller's."""
        if self._plan is None:
            return {"donate_argnums": (2,)}
        work = 0 if self._master_bufs is None else 1
        return {"donate_argnums": tuple(
                    i for i in (0, 1, 2)
                    if self._donate_work or i != work),
                "keep_unused": True}

    def _adopt(self, params, masters):
        """Pack ``params`` (and ``masters``) under the current plan
        into buffers of the optimizer's own.  ``params`` stays as the
        cached tree the model reads; the masters' tree is not kept —
        the ``masters`` property unpacks on demand, as after a step."""
        self._param_bufs = _own_pack(self._plan.pack_model(params), params)
        self._master_bufs = (
            _own_pack(self._plan.pack_work(masters), masters)
            if masters is not None else None)
        self._params_cache = params
        self._masters_cache = None
        self._unpack_model_jit = jax.jit(self._plan.unpack_model)
        self._unpack_work_jit = jax.jit(self._plan.unpack)

    # ---- packed views ----------------------------------------------------
    @property
    def params(self) -> Pytree:
        """The current params pytree.  On the bucketed path this unpacks
        lazily — ONE compiled slice-and-reshape program per step, cached
        until the next step — so the packed buffers stay the canonical
        representation."""
        if self._plan is None:
            return self._params_tree
        if self._params_cache is None:
            with span("apex/optim/unpack_model"):
                self._params_cache = self._unpack_model_jit(
                    self._param_bufs)
        return self._params_cache

    @params.setter
    def params(self, value: Pytree):
        if self._plan is None:
            self._params_tree = value
        else:
            self._param_bufs = _own_pack(self._plan.pack_model(value),
                                         value)
            self._params_cache = value

    @property
    def masters(self) -> Optional[Pytree]:
        if self._plan is None:
            return self._masters_tree
        if self._master_bufs is None:
            return None
        if self._masters_cache is None:
            with span("apex/optim/unpack_model"):
                self._masters_cache = self._unpack_work_jit(
                    self._master_bufs)
        return self._masters_cache

    @masters.setter
    def masters(self, value: Optional[Pytree]):
        if self._plan is None:
            self._masters_tree = value
        elif value is None:
            self._master_bufs = None
            self._masters_cache = None
        else:
            self._master_bufs = _own_pack(self._plan.pack_work(value),
                                          value)
            self._masters_cache = None

    # ---- functional core -------------------------------------------------
    def init_state(self, params: Pytree) -> Pytree:
        raise NotImplementedError

    def init_state_packed(self, plan: BucketPlan, work: Pytree) -> Pytree:
        """Packed optimizer state, built packed: each field of the
        per-leaf state, bucket-packed (param-shaped fields -> flat
        buffers; per-tensor scalar fields -> one (num leaves,) vector
        per bucket).  ``init_state`` and the packs are ONE program over
        the work tree whose outputs are the buffers, so no per-leaf
        state tree is ever live beside them."""
        return jax.jit(lambda work: {
            k: plan.pack_state_field(v)
            for k, v in self.init_state(work).items()})(work)

    def _step_math(self, params, grads, opt_state, step, grad_scale, hypers):
        """Pure per-leaf update on the (possibly master) params."""
        raise NotImplementedError

    def _flat_bucket_step(self, bucket_index: int, p, g, state, step,
                          grad_scale, hypers, extra, keep=None,
                          model_dtype=None):
        """One bucket's flat update: ``p``/``g`` are flat buffers,
        ``state`` maps field name -> this bucket's buffer.  Returns
        (new_p, new_state).  ``extra`` is whatever ``_flat_prologue``
        returned (e.g. LAMB's global-norm clip coefficient).  ``keep``
        (``_keep_flag``) is the overflow skip, taken inside the
        update's own sweep; ``model_dtype`` asks the sweep that writes
        ``new_p`` for its copy in that dtype as well, and the result is
        then (new_p, new_state, new_model_p)."""
        raise NotImplementedError

    def _flat_prologue(self, work_bufs, grad_bufs, step, grad_scale,
                       hypers):
        """Cross-bucket prologue for the flat path (default: nothing)."""
        return None

    def _flat_step_math(self, work_bufs, grad_bufs, opt_state, step,
                        grad_scale, hypers, keep=None, cast_model=False):
        """Every bucket's update, each buffer read once and written
        once per phase: the overflow skip (``keep``) and, with
        ``cast_model``, the model-dtype copy of a bucket that has
        masters ride the update's sweep.  -> (new work buffers, new
        state, new model-dtype buffers or None)."""
        # fp8 delayed-scaling slots are carried state, not optimizer
        # math: split them out of the per-bucket loop and update them
        # from the POST-step work buffers below (delayed scaling: the
        # scale the next forward quantizes with reflects this step's
        # weights)
        fp8_state = {k: opt_state[k] for k in _FP8_SLOTS
                     if k in opt_state}
        core = {k: v for k, v in opt_state.items()
                if k not in fp8_state}
        with jax.named_scope("apex_optim/grad_norm"):
            extra = self._flat_prologue(work_bufs, grad_bufs, step,
                                        grad_scale, hypers)
        new_bufs: List[Any] = []
        new_model: List[Any] = []
        new_state: Dict[str, List[Any]] = {k: [] for k in core}
        for bi, (p, g) in enumerate(zip(work_bufs, grad_bufs)):
            bucket_state = {k: v[bi] for k, v in core.items()}
            bucket = self._plan.buckets[bi]
            has_masters = cast_model and bucket.model_dtype != bucket.dtype
            np_, ns, *nm = self._flat_bucket_step(
                bi, p, g, bucket_state, step, grad_scale, hypers, extra,
                keep, bucket.model_dtype if has_masters else None)
            new_bufs.append(np_)
            new_model.extend(nm or [np_])
            for k in new_state:
                new_state[k].append(ns[k])
        if fp8_state:
            with jax.named_scope("apex_optim/fp8_slots"):
                new_fp8 = self._fp8_slot_update(new_bufs, fp8_state, step)
            if keep is not None:
                # (num_leaves,)-sized: a select of their own
                with jax.named_scope("apex_optim/skip_select"):
                    new_fp8 = _select(keep, new_fp8, fp8_state)
            new_state.update(new_fp8)
        return new_bufs, new_state, new_model if cast_model else None

    def _fp8_slot_update(self, new_work_bufs, fp8_state, step):
        """The packed fp8 weight-scale slots' delayed-scaling
        transition over the post-step work buffers, riding the step's
        own jit and donation — the same shared per-bucket pass as the
        pipeline's gradient-side state (``amp.fp8.update_packed``),
        gated by the step clock instead of an Fp8State counter (a
        skipped step's held clock therefore also holds the fp8
        cadence)."""
        from apex_tpu.amp.fp8 import update_packed
        policy = getattr(self, "fp8_policy", None)
        if policy is None:              # foreign slots: carry through
            return fp8_state
        do = jnp.equal(jnp.asarray(step, jnp.int32)
                       % jnp.int32(policy.interval), 0)
        hist, scale, _ = update_packed(
            fp8_state["fp8_amax_history"], fp8_state["fp8_scale"],
            new_work_bufs, self._plan, policy, update=do,
            scale_min_metric="fp8/weight_scale_min")
        return {"fp8_amax_history": hist, "fp8_scale": scale}

    # ---- fp8 delayed-scaling slots ---------------------------------------
    def enable_fp8(self, policy=None) -> None:
        """Attach packed fp8 delayed-scaling state for the WEIGHTS as
        optimizer slots (``fp8_amax_history``: (n_leaves, H) per
        bucket; ``fp8_scale``: (n_leaves,) per bucket) — donated to
        the jitted step, offloaded, checkpointed (v1 and v2) and
        re-chunked like every other slot.  The step updates them from
        the post-update work buffers; read the current per-leaf
        scales with :meth:`fp8_scales` and feed them to
        ``fused_dense.fp8_matmul(w_scale=...)``.  Requires the
        bucketed path."""
        if self._plan is None:
            raise ValueError(
                "enable_fp8 requires the bucketed path "
                "(fuse_buckets=False or the packer declined this "
                "tree)")
        from apex_tpu.amp.fp8 import Fp8Policy, init_state
        if policy is None:
            policy = Fp8Policy()
        self.fp8_policy = policy
        st = init_state(self._plan, policy)
        slots = {"fp8_amax_history": list(st.amax_history),
                 "fp8_scale": list(st.scale)}
        if self.offload_state:
            slots = place_on_host(slots)
        # a new opt_state STRUCTURE: the jitted step re-traces on the
        # next call (jit keys on pytree structure), no re-jit needed
        self.opt_state = {**self.opt_state, **slots}

    def fp8_scales(self, opt_state=None) -> Pytree:
        """Per-leaf pytree of the current fp8 weight scales (scalar
        slices of the packed slot — they fuse into the caller's jit).
        Pass the ``opt_state`` threaded through an embedded
        ``functional_step`` loop, or omit it for the stateful
        facade's own state."""
        if self._plan is None or not hasattr(self, "fp8_policy"):
            raise ValueError("enable_fp8 was not called")
        state = self.opt_state if opt_state is None else opt_state
        from apex_tpu.amp.fp8 import Fp8State, scales_tree
        st = Fp8State(amax_history=list(state["fp8_amax_history"]),
                      scale=list(state["fp8_scale"]),
                      step=self.step_count)
        return scales_tree(self._plan, st)

    def _full_step(self, params, masters, opt_state, grads, step, grad_scale,
                   hypers, found_inf=None):
        work = masters if masters is not None else params
        new_work, new_state = self._step_math(
            work, grads, opt_state, step, grad_scale, hypers)
        if found_inf is not None:
            new_work, new_state = _skip_on_overflow(
                found_inf, new_work, work, new_state, opt_state)
        if masters is not None:
            with jax.named_scope("apex_optim/cast_model"):
                new_params = tree_map(
                    lambda p, m: m.astype(p.dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else m,
                    params, new_work)
            return new_params, new_work, new_state
        return new_work, None, new_state

    def _full_step_flat(self, param_bufs, master_bufs, opt_state, grads,
                        step, grad_scale, hypers, found_inf=None):
        """Bucketed step body: grads pack (one concatenate per bucket)
        — or arrive ALREADY packed from the flat AMP pipeline, in which
        case zero pack work happens here — then ONE chain of sweeps
        per bucket; params/masters/state go in and come out packed.
        The overflow skip and the model-dtype copy of the masters are
        part of that chain (``_flat_step_math``), not passes after it."""
        work_bufs = master_bufs if master_bufs is not None else param_bufs
        if self._plan.is_packed(grads):
            grad_bufs = list(grads)
        else:
            with jax.named_scope("apex_optim/pack_grads"):
                grad_bufs = self._plan.pack(grads)
        new_work, new_state, new_params = self._flat_step_math(
            work_bufs, grad_bufs, opt_state, step, grad_scale, hypers,
            _keep_flag(found_inf), cast_model=master_bufs is not None)
        if master_bufs is not None:
            return new_params, new_work, new_state
        return new_work, None, new_state

    def _full_step_offload(self, params, masters, opt_state, grads, step,
                           grad_scale, hypers, found_inf=None):
        """TPU fused-offload step body: pull state from pinned host at
        the top (whole flat buffers on the bucketed path); out_shardings
        push the new state back."""
        opt_state = tree_map(
            lambda x: jax.device_put(x, _DEVICE_MEMORY), opt_state)
        return self._full_step_impl(params, masters, opt_state, grads,
                                    step, grad_scale, hypers, found_inf)

    def _state_is_packed(self, opt_state) -> bool:
        """True only for the plan's OWN packed layout: every field is a
        per-bucket list whose buffers structurally match the plan (1-D,
        bucket-sized flat or per-leaf-scalar vector).  A per-leaf state
        pytree that merely happens to be a list of the right length
        (e.g. list-shaped params) must not be mistaken for packed."""
        if self._plan is None or not isinstance(opt_state, dict) \
                or not opt_state:
            return False
        buckets = self._plan.buckets
        for field in opt_state.values():
            if not isinstance(field, (list, tuple)) \
                    or len(field) != len(buckets):
                return False
            for buf, b in zip(field, buckets):
                if getattr(buf, "ndim", None) == 2 \
                        and buf.shape[0] == len(b.leaves):
                    continue    # row-stacked per-leaf vectors (fp8)
                if getattr(buf, "ndim", None) != 1:
                    return False
                if tuple(buf.shape) not in ((b.size,), (len(b.leaves),)):
                    return False
        return True

    def functional_step(self, params, opt_state, grads, step,
                        grad_scale=1.0, clip_coef=None, found_inf=None):
        """Embed-in-your-own-jit entry point (no master handling).

        ``params``/``grads`` are pytrees; ``opt_state`` may be either a
        per-leaf state pytree (per-leaf math runs) or this optimizer's
        PACKED state (e.g. ``opt.opt_state`` of a bucketed optimizer) —
        then the flat bucket sweeps run, the new state comes back
        packed, and the new params come back as a pytree (what a train
        step's model apply needs anyway; the repack/unpack concatenates
        and slices fuse into the caller's jit).  With packed state,
        ``grads`` may also arrive as the plan's per-bucket flat buffers
        (the flat AMP pipeline's layout) — no pack happens then — or as
        an ``amp.FlatGrads`` bundle, whose ``found_inf``/``clip_coef``
        apply unless overridden explicitly (``step()`` parity).

        ``clip_coef``: optional traced global-norm clip coefficient
        (e.g. ``FlatGrads.clip_coef``); folded into the update's grad
        scaling, so clipping never materializes a gradient copy.

        ``found_inf``: optional on-device overflow flag; when nonzero,
        params and state come back unchanged (branch-free skip — the
        caller owns the step clock and should likewise not advance it
        on a skipped step, as ``step()`` does)."""
        packed = self._state_is_packed(opt_state)
        if hasattr(grads, "bufs") and hasattr(grads, "found_inf"):
            # amp.FlatGrads (duck-typed, as in step())
            if not packed:
                raise ValueError(
                    "FlatGrads require the bucketed path — this call "
                    "runs per-leaf state; pass a gradient pytree "
                    "instead")
            if found_inf is None:
                found_inf = grads.found_inf
            if clip_coef is None:
                clip_coef = getattr(grads, "clip_coef", None)
            grads = grads.bufs
        gs = _fold_clip(grad_scale, clip_coef)
        hypers = dict(self.hypers)
        if packed:
            with jax.named_scope("apex_optim/pack_grads"):
                work_bufs = self._plan.pack_work(params)
                grad_bufs = (list(grads) if self._plan.is_packed(grads)
                             else self._plan.pack(grads))
            new_bufs, new_state, _ = self._flat_step_math(
                work_bufs, grad_bufs, opt_state, step, gs, hypers,
                _keep_flag(found_inf))
            return self._plan.unpack(new_bufs), new_state
        new_params, new_state = self._step_math(
            params, grads, opt_state, step, gs, hypers)
        if found_inf is not None:
            new_params, new_state = _skip_on_overflow(
                found_inf, new_params, params, new_state, opt_state)
        return new_params, new_state

    # ---- stateful facade -------------------------------------------------
    def step(self, grads: Pytree, grad_scale=1.0, found_inf=None,
             clip_coef=None) -> Pytree:
        """Apply one update; returns (and stores) the new params.

        ``grads`` may be the usual pytree, the plan's per-bucket flat
        buffers (the flat AMP pipeline's pack-once layout — no re-pack
        happens), or an ``amp.FlatGrads`` bundle, whose ``found_inf``
        and ``clip_coef`` are used unless overridden explicitly.

        ``found_inf``: optional on-device i32/bool scalar (amp's overflow
        flag from ``scaled_value_and_grad`` or ``flat_scale``).  When
        given and nonzero, params/masters/state keep their old values
        and the step count does not advance — a branch-free skip, never
        a host sync.

        ``clip_coef``: optional traced global-norm clip coefficient in
        (0, 1]; folded into the update's grad scaling (see
        ``_fold_clip``) so clipping costs zero extra gradient passes."""
        mark_step()
        with span("apex/optim/step"):
            return self._step(grads, grad_scale, found_inf, clip_coef)

    def _step(self, grads, grad_scale, found_inf, clip_coef):
        """``step``'s body, in the host spans ``apex/optim/args``,
        ``clock``, ``dispatch`` (children of ``apex/optim/step``)."""
        if hasattr(grads, "bufs") and hasattr(grads, "found_inf"):
            # amp.FlatGrads (duck-typed: amp must stay import-light here)
            if self._plan is None:
                raise ValueError(
                    "FlatGrads/packed gradients require the bucketed "
                    "path — this optimizer runs per-leaf "
                    "(fuse_buckets=False or the packer declined its "
                    "tree); pass a gradient pytree instead")
            if found_inf is None:
                found_inf = grads.found_inf
            if clip_coef is None:
                clip_coef = getattr(grads, "clip_coef", None)
            grads = grads.bufs
        with span("apex/optim/clock"):
            self.step_count = self.step_count + 1
        with span("apex/optim/args"):
            grad_scale = _fold_clip(grad_scale, clip_coef)
            eager_offload = self.offload_state and not self._fused_offload
            args = self._step_args(grads, grad_scale, found_inf)
            if eager_offload:   # CPU fallback: explicit round trip
                args = args[:2] + (place_on_device(args[2]),) + args[3:]
            # bucketed path only: its buffers are whole by construction
            # (the packer declines sharded leaves); per-leaf math
            # partitions under plain jit
            mesh = _replica_mesh(grads) if self._plan is not None else None
            step_fn = (self._jit_step if mesh is None
                       else self._replicated_step(mesh))
        # the one call that can block: the donated state of the
        # previous step may still be in use on the device
        with span("apex/optim/dispatch"):
            new_params, new_masters, self.opt_state = step_fn(*args)
        if self._plan is not None:
            self._param_bufs, self._master_bufs = new_params, new_masters
            self._params_cache = None
            self._masters_cache = None
        else:
            self._params_tree, self._masters_tree = new_params, new_masters
        if eager_offload:
            self.opt_state = place_on_host(self.opt_state)
        if found_inf is not None:
            # a skipped step must not advance the bias-correction clock
            with span("apex/optim/clock"):
                self.step_count = jnp.where(jnp.asarray(found_inf) > 0,
                                            self.step_count - 1,
                                            self.step_count)
        return self.params

    def _replicated_step(self, mesh):
        """The step program for gradients that arrive replicated over
        ``mesh`` — the data-parallel layout: every device holds the
        full params and runs the same update after the reduction.
        The same body runs under ``shard_map`` with every operand
        replicated, so no partitioner decides a bucket's layout (and a
        Mosaic kernel in a step body, which a plain multi-device jit
        refuses outright, would still run)."""
        fn = self._mesh_steps.get(mesh)
        if fn is None:
            spec = jax.sharding.PartitionSpec()
            fn = self._mesh_steps[mesh] = jax.jit(
                jax.shard_map(self._full_step_impl, mesh=mesh,
                              in_specs=spec, out_specs=spec,
                              check_vma=False),
                **self._donation)
        return fn

    def _step_args(self, grads, grad_scale=1.0, found_inf=None):
        """The positional arguments of one ``_jit_step`` call on the
        current state (``step()`` builds its call with this; lowering
        the step program for inspection needs exactly the same)."""
        traced_hypers = {
            k: jnp.asarray(v, jnp.float32) if isinstance(v, float) else v
            for k, v in self.hypers.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
        params, masters = ((self._param_bufs, self._master_bufs)
                           if self._plan is not None else
                           (self._params_tree, self._masters_tree))
        return (params, masters, self.opt_state, grads, self.step_count,
                jnp.asarray(grad_scale, jnp.float32), traced_hypers,
                found_inf)

    def zero_grad(self):
        """No-op for parity: JAX grads are freshly computed, never stored."""

    def grad_accum_init(self):
        """Fresh zeroed microbatch gradient-accumulation state in this
        optimizer's bucket layout (``amp.GradAccum``): per-bucket f32
        accumulator buffers + the cross-microbatch found_inf latch +
        the microbatch count.  Thread it through
        ``FlatGradPipeline.accumulate()`` per microbatch and hand the
        ``finalize()`` result to ``step(flat, found_inf=...)`` — a
        latched overflow skips the whole committed step and holds the
        step clock, exactly like a single-batch overflow.  Requires
        the bucketed path (the accumulators ARE bucket buffers)."""
        if self._plan is None:
            raise ValueError(
                "grad_accum_init requires the bucketed path "
                "(fuse_buckets=False or the packer declined this "
                "tree); accumulate per leaf with "
                "amp.scaled_value_and_grad(microbatches=N) instead")
        from apex_tpu.amp.flat_pipeline import GradAccum
        return GradAccum.zeros(self._plan)

    # ---- elastic re-chunking (fleet resize) ------------------------------
    def rechunk(self, max_bucket_bytes) -> bool:
        """Rebuild the :class:`BucketPlan` with a new
        ``max_bucket_bytes`` chunking cap and repack the LIVE training
        state (params, masters, every optimizer-state field) into the
        new layout.

        The elastic-resize hook: when the per-host HBM share changes
        because the fleet grew or shrank (``run_elastic``'s
        ``grow_max_bucket_bytes=``), the overlap schedule's chunk size
        should track it (docs/perf.md).  Chunk boundaries always fall
        on leaf boundaries, so the update math is bit-identical across
        layouts — only the packing changes (the chunked-vs-monolithic
        equivalence the overlap schedule already pins).  One eager
        per-leaf unpack + repack per resize — a rare event by
        construction.  Offloaded state round-trips through device for
        the repack and lands back on host.  Callers holding a
        ``FlatGradPipeline`` bound to the old plan must rebuild it
        (the pipeline snapshots the plan at construction).  Returns
        False (no-op) when the cap already matches."""
        if self._plan is None:
            raise RuntimeError(
                "rechunk requires the bucketed path (fuse_buckets="
                "True and a tree the packer accepted)")
        if max_bucket_bytes == self._plan.max_bucket_bytes:
            return False
        params = self.params              # cached lazy unpack
        masters = self.masters
        state = self.opt_state
        if self.offload_state:
            state = place_on_device(state)
        state_trees = {k: self._plan.unpack_state_field(v)
                       for k, v in state.items()}
        work = masters if masters is not None else params
        self._plan = BucketPlan.from_tree(
            work, params if masters is not None else None,
            max_bucket_bytes=max_bucket_bytes)
        self._adopt(params, masters)
        self.opt_state = {
            k: _own_pack(self._plan.pack_state_field(v), v)
            for k, v in state_trees.items()}
        if self.offload_state:
            self.opt_state = place_on_host(self.opt_state)
        # fresh jit: the step body closes over the plan
        self._jit_step = self._build_jit_step()
        return True

    # ---- bucket-native checkpoint capture --------------------------------
    def packed_snapshot(self):
        """Checkpoint capture that NEVER unpacks: one async device-side
        copy per packed buffer (params, masters, every optimizer-state
        field), plus host scalars — the bucket-native checkpoint v2
        input (``checkpoint.save_training_state`` routes here when the
        optimizer runs bucketed).

        The copies are the double-buffer: the caller's next ``step()``
        donates ``opt_state`` (and rebinds the param buffers), so an
        in-flight device->host transfer must read from buffers the step
        cannot delete.  ``plan.unpack`` is never called — the whole
        point of the format (ISSUE 6 acceptance: zero per-leaf work).

        Returns ``{"step", "hypers", "plan", "param_bufs",
        "master_bufs", "state"}`` with jax-array buffer lists.  Raises
        ``ValueError`` on a per-leaf optimizer — callers fall back to
        ``state_dict()`` / the v1 format there."""
        if self._plan is None:
            raise ValueError(
                "packed_snapshot requires the bucketed path "
                "(fuse_buckets=False or the packer declined this tree);"
                " use state_dict() / the v1 checkpoint format instead")
        # offloaded state copies IN PLACE on the host (buf.copy()
        # preserves placement; the "d2h" later is a plain host memcpy)
        # — pulling it into HBM first would allocate the very
        # state-size the offload exists to avoid
        state = self.opt_state
        return {
            "step": int(self.step_count),
            "hypers": dict(self.hypers),
            "plan": self._plan,
            "param_bufs": [_device_copy(b) for b in self._param_bufs],
            "master_bufs": ([_device_copy(b) for b in self._master_bufs]
                            if self._master_bufs is not None else None),
            "state": {k: [_device_copy(b) for b in v]
                      for k, v in state.items()},
        }

    def load_packed_snapshot(self, step, hypers, param_bufs, master_bufs,
                             state):
        """Inverse of :meth:`packed_snapshot` — adopt packed buffers
        directly (one host->device put per bucket, zero per-leaf
        traffic).  Buffers may be numpy (fresh from a checkpoint read)
        or jax arrays; the caller has already validated the layout
        against this optimizer's plan (checkpoint.py compares the v2
        header's plan doc with ``self._plan.layout()``)."""
        if self._plan is None:
            raise ValueError(
                "load_packed_snapshot requires the bucketed path")
        self.step_count = jnp.int32(step)
        self.hypers.update(hypers)
        # copied in: the step donates what it is given here, and the
        # caller's snapshot must stay readable (and loadable again)
        self._param_bufs = [_owned(b) for b in param_bufs]
        self._master_bufs = ([_owned(b) for b in master_bufs]
                             if master_bufs is not None else None)
        self._params_cache = None
        self._masters_cache = None
        # the v2 payload stores every state buffer flattened; a
        # non-flat slot (the fp8 (n_leaves, H) amax history) adopts the
        # LIVE slot's shape back — same element count, the layout
        # check upstream already matched the plan
        old = self.opt_state

        def _shaped(b, o):
            # metadata-only reshape (numpy and jax alike): never a
            # copy, never an extra device placement
            want = (tuple(o.shape)
                    if o is not None and hasattr(o, "shape") else None)
            if want is not None \
                    and tuple(getattr(b, "shape", ())) != want \
                    and getattr(b, "size", None) == o.size:
                b = b.reshape(want)
            return b

        if self.offload_state:
            # adopt each buffer straight onto the existing (host)
            # placement — asarray-then-place_on_host would stage the
            # whole state in HBM, the state-size spike offloading
            # exists to avoid (the load_state_dict mirror of the
            # packed_snapshot in-place rule)
            self.opt_state = {
                k: [jax.device_put(_shaped(b, o), o.sharding)
                    for b, o in zip(v, old[k])]
                for k, v in state.items()}
        else:
            self.opt_state = {
                k: [_owned(_shaped(b, o))
                    for b, o in zip(v, old.get(k, [None] * len(v)))]
                for k, v in state.items()}

    # ---- serialization (torch Optimizer.state_dict shape) ---------------
    def state_dict(self):
        if self._plan is not None:
            # unpack to the per-leaf checkpoint layout (unchanged across
            # packing, so per-leaf and bucketed optimizers interload).
            # The slices are fresh buffers — safe against the next
            # step()'s donation of the packed state.
            state = self.opt_state
            if self.offload_state:
                state = place_on_device(state)
            state_tree = {k: self._plan.unpack_state_field(v)
                          for k, v in state.items()}
            return {
                "step": int(self.step_count),
                "hypers": dict(self.hypers),
                "state": state_tree,
                "masters": self.masters,
            }
        # copy the state out: the next step() DONATES self.opt_state to
        # the compiled update, which deletes the buffers a by-reference
        # snapshot would still point at ("Array has been deleted" at
        # serialization time)
        return {
            "step": int(self.step_count),
            "hypers": dict(self.hypers),
            "state": tree_map(
                lambda x: jnp.array(x, copy=True)
                if isinstance(x, jax.Array) else x, self.opt_state),
            "masters": self.masters,
        }

    def load_state_dict(self, sd):
        self.step_count = jnp.int32(sd["step"])
        self.hypers.update(sd["hypers"])
        if self._plan is not None:
            # per-leaf checkpoint layout -> packed buffers of our own:
            # the checkpoint dict is never aliased by the donating step
            self.opt_state = {
                k: _own_pack(self._plan.pack_state_field(v), v)
                for k, v in sd["state"].items()}
        else:
            # copy: step() donates opt_state to the compiled update, and
            # the caller's checkpoint dict must stay readable after we
            # step
            self.opt_state = tree_map(
                lambda x: jnp.array(x, copy=True)
                if isinstance(x, jax.Array) else x, sd["state"])
        if self.offload_state:
            # restore must respect the host-residency invariant NOW —
            # waiting for the next step to re-home it would leave the
            # full f32 state in HBM at exactly the tight-memory moment
            # offloading exists for
            self.opt_state = place_on_host(self.opt_state)
        if sd.get("masters") is not None:
            self.masters = sd["masters"]

    # hyper access in the torch param_group idiom: opt.lr = ...
    @property
    def lr(self):
        return self.hypers["lr"]

    @lr.setter
    def lr(self, value):
        self.hypers["lr"] = value

    def _merge_hypers(self, traced_hypers):
        """Traced float hypers override statics inside the jitted step."""
        merged = dict(self.hypers)
        merged.update(traced_hypers)
        return merged
