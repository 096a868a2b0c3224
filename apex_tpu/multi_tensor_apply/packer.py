"""One-time bucket packing for the fused optimizers.

The reference's ``multi_tensor_apply`` re-chunks the tensor lists on
every step (cheap on CUDA — it's host pointer math).  On TPU the analog
must not re-trace or re-concatenate per step, so the plan is computed
ONCE at optimizer init: dtype-homogeneous parameter leaves are assigned
to buckets, each bucket a single flat HBM buffer with static
shape+offset metadata.  The jitted optimizer step then runs one chain
of sweeps per bucket (see apex_tpu.ops.multi_tensor), and the packed
buffers are the persistent representation — params, masters and
optimizer state stay packed BETWEEN steps.  Unpacking (static
``lax.slice`` + reshape per leaf, offsets are Python ints) happens only
on the rare host-facing paths: ``state_dict()``, ``load_state_dict()``
and the ``params`` property.

A bucket that holds ONE leaf keeps that leaf's shape (``Bucket.shape``)
for every buffer of it: its pack is the leaf, its unpack the buffer.
Nothing in such a bucket needs the flat layout (its one segment is the
whole buffer, and every update is elementwise or per leaf), while on
TPU raveling a tiled (R, C) matrix into a 1-D buffer is a relayout
XLA pays as copies on every pack and unpack.

Per-tensor semantics (LAMB trust ratios, NovoGrad per-tensor second
moments) survive packing through each bucket's ``segment_sizes``: the
leaves' element counts as Python ints, in buffer order, so the
segmented kernels reduce and broadcast over static slices.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


class LeafSpec(NamedTuple):
    index: int            # position in tree_leaves order
    shape: Tuple[int, ...]
    size: int             # element count
    offset: int           # element offset inside the bucket buffer


class Bucket(NamedTuple):
    dtype: Any            # work (stepped) dtype of every leaf in here
    model_dtype: Any      # model-param dtype (== dtype without masters)
    leaves: Tuple[LeafSpec, ...]
    size: int             # total element count (exact, unpadded)

    @property
    def leaf_shaped(self) -> bool:
        """True for a bucket of one (non-scalar) leaf: its buffers keep
        the leaf's shape instead of the flat 1-D layout."""
        return len(self.leaves) == 1 and len(self.leaves[0].shape) > 0

    @property
    def shape(self) -> Tuple[int, ...]:
        """The shape of every parameter-mirroring buffer of the
        bucket: the leaf's own where ``leaf_shaped``, else 1-D."""
        return self.leaves[0].shape if self.leaf_shaped else (self.size,)


class PackedState(dict):
    """An optimizer state in a plan's packed layout: field name -> one
    buffer per bucket.  A dict of a type of its own, so the layout is
    known without reading shapes: a per-leaf state of a plain-list
    params tree, each leaf alone in a bucket, has the same structure
    and shapes, in the leaf order rather than the bucket order."""


def _packed_state_keys(d):
    keys = tuple(sorted(d))
    return [(jax.tree_util.DictKey(k), d[k]) for k in keys], keys


jax.tree_util.register_pytree_with_keys(
    PackedState, _packed_state_keys,
    lambda keys, values: PackedState(zip(keys, values)),
    flatten_func=lambda d: ([d[k] for k in sorted(d)], tuple(sorted(d))))


def _leaf_arrays(tree) -> List[jax.Array]:
    return jax.tree_util.tree_leaves(tree)


class BucketPlan:
    """Static packing plan for one params pytree.

    Built from the WORK tree (masters when mixed-precision, else the
    params themselves) plus, when masters exist, the model params tree
    — buckets are keyed on (work dtype, model dtype) so the
    master->model writeback stays a single-dtype cast per bucket.
    """

    def __init__(self, treedef, buckets: Sequence[Bucket],
                 max_bucket_bytes: Optional[int] = None):
        self.treedef = treedef
        self.buckets = tuple(buckets)
        self.n_leaves = sum(len(b.leaves) for b in self.buckets)
        # the chunking cap this plan was built with (None = monolithic
        # per dtype group) — consumers that were ASKED for a specific
        # cap can detect a mismatching supplied plan (FlatGradPipeline)
        self.max_bucket_bytes = max_bucket_bytes

    # ---- construction ----------------------------------------------------
    @classmethod
    def from_tree(cls, work: Pytree, model: Optional[Pytree] = None,
                  max_bucket_bytes: Optional[int] = None
                  ) -> Optional["BucketPlan"]:
        """Build a plan, or None when packing is unsupported: empty
        trees, non-floating leaves (nothing for an optimizer kernel to
        do with them), or multi-device leaves (concatenation would
        destroy their sharding — the per-leaf path preserves it).

        ``max_bucket_bytes``: optional chunking cap.  By default every
        dtype group packs into ONE bucket — maximal kernel fusion, but
        the data-parallel all-reduce then depends on the ENTIRE
        backward (one trailing collective).  With a cap, a dtype group
        splits into multiple buckets of at most that many bytes (leaf
        order preserved, every bucket holds >= 1 leaf), so each
        bucket's collective depends only on its own leaves' cotangents
        and the scheduler can overlap bucket k's psum with bucket
        k-1's backward compute (docs/perf.md "Overlap schedule")."""
        work_leaves, treedef = jax.tree_util.tree_flatten(work)
        if not work_leaves:
            return None
        model_leaves = (jax.tree_util.tree_leaves(model)
                        if model is not None else work_leaves)
        if len(model_leaves) != len(work_leaves):
            return None
        groups = {}
        for i, (w, p) in enumerate(zip(work_leaves, model_leaves)):
            if not (hasattr(w, "dtype") and hasattr(w, "shape")):
                return None
            if not jnp.issubdtype(w.dtype, jnp.floating):
                return None
            if isinstance(w, jax.Array):
                try:
                    multi = len(w.sharding.device_set) > 1
                except AttributeError:
                    # tracer (cached_plan inside a jit trace): sharding
                    # unknown — the caller owns that placement decision
                    multi = False
                if multi:
                    return None
            key = (jnp.dtype(w.dtype), jnp.dtype(p.dtype))
            groups.setdefault(key, []).append((i, w))
        buckets = []
        for (wdt, mdt), entries in groups.items():
            cap_elems = None
            if max_bucket_bytes is not None:
                cap_elems = max(1, int(max_bucket_bytes)
                                // jnp.dtype(wdt).itemsize)
            specs, offset = [], 0
            for i, w in entries:
                size = int(np.prod(w.shape)) if w.shape else 1
                if cap_elems is not None and specs \
                        and offset + size > cap_elems:
                    # start a fresh bucket: the cap is a soft split
                    # point, never a reason to split one leaf
                    buckets.append(Bucket(wdt, mdt, tuple(specs), offset))
                    specs, offset = [], 0
                specs.append(LeafSpec(i, tuple(w.shape), size, offset))
                offset += size
            buckets.append(Bucket(wdt, mdt, tuple(specs), offset))
        return cls(treedef, buckets, max_bucket_bytes=max_bucket_bytes)

    # ---- packing ---------------------------------------------------------
    def pack(self, tree: Pytree, dtypes=None) -> List[jax.Array]:
        """Pytree -> one buffer per bucket.  Trace-safe (the jitted
        step packs the incoming grads this way: one concatenate per
        bucket, not per leaf; a leaf-shaped bucket's buffer is its
        leaf).  ``dtypes``: per-bucket target dtype (defaults to
        whatever concatenation yields — homogeneous inputs keep their
        dtype)."""
        leaves = _leaf_arrays(tree)
        out = []
        for bi, b in enumerate(self.buckets):
            if b.leaf_shaped:
                # the leaf itself (the reshape only makes an array of
                # a host one)
                buf = jnp.reshape(leaves[b.leaves[0].index], b.shape)
            else:
                buf = jnp.concatenate(
                    [jnp.ravel(leaves[s.index]) for s in b.leaves])
            want = dtypes[bi] if dtypes is not None else None
            if want is not None and buf.dtype != want:
                buf = buf.astype(want)
            out.append(buf)
        return out

    def pack_work(self, tree: Pytree) -> List[jax.Array]:
        return self.pack(tree, dtypes=[b.dtype for b in self.buckets])

    def pack_model(self, tree: Pytree) -> List[jax.Array]:
        return self.pack(tree, dtypes=[b.model_dtype for b in self.buckets])

    def pack_grads(self, tree: Pytree) -> List[jax.Array]:
        """THE gradient pack: one concatenate per bucket, grads keep
        their own (model) dtype — the flat AMP pipeline's single pack
        point.  Everything downstream (bucketed all-reduce, fused
        unscale+norm, the flat optimizer kernels) consumes these
        buffers; nothing re-walks the pytree."""
        return self.pack(tree)

    def is_packed(self, obj) -> bool:
        """True iff ``obj`` is a per-bucket buffer list matching this
        plan: one buffer per bucket, each of the bucket's shape, and
        not a tree of this plan (``reads_as_tree``).  Shape-only (works
        on tracers); used by step() and the flat pipeline to accept
        already-packed gradients without re-packing."""
        if not isinstance(obj, (list, tuple)) \
                or len(obj) != len(self.buckets):
            return False
        return all(tuple(getattr(buf, "shape", ())) == b.shape
                   for buf, b in zip(obj, self.buckets)) \
            and not self.reads_as_tree(obj)

    def reads_as_tree(self, obj) -> bool:
        """True where ``obj`` has this plan's own tree structure.  Only
        a params tree that is a plain list of arrays, each alone in a
        bucket of its shape, has the structure of its packed buffers
        too, and the bucket order (dtype groups first) need not be the
        leaf order: such an object is read as the tree.  Packed buffers
        known as such go in as the tree of them (``unpack_grads``: a
        reorder, no copy), or, for an optimizer state, as a
        ``PackedState``."""
        return jax.tree_util.tree_structure(obj) == self.treedef

    # ---- unpacking -------------------------------------------------------
    def _unpack_leaves(self, bufs: Sequence[Optional[jax.Array]],
                       dtypes=None) -> List[Optional[jax.Array]]:
        """Per-leaf arrays in leaf-index order; the leaves of a bucket
        whose buffer is None stay None."""
        leaves: List[Optional[jax.Array]] = [None] * self.n_leaves
        for bi, b in enumerate(self.buckets):
            buf = bufs[bi]
            if buf is None:
                continue
            want = dtypes[bi] if dtypes is not None else None
            for s in b.leaves:
                if b.leaf_shaped:
                    leaf = jnp.reshape(buf, s.shape)  # the buffer itself
                else:
                    # static offsets -> lax.slice: XLA sees fixed layout
                    leaf = jax.lax.slice(
                        buf, (s.offset,),
                        (s.offset + s.size,)).reshape(s.shape)
                if want is not None and leaf.dtype != want:
                    leaf = leaf.astype(want)
                leaves[s.index] = leaf
        return leaves

    def unpack(self, bufs: Sequence[jax.Array]) -> Pytree:
        """Per-bucket flat buffers -> pytree in the WORK dtypes."""
        return jax.tree_util.tree_unflatten(
            self.treedef,
            self._unpack_leaves(bufs, [b.dtype for b in self.buckets]))

    def unpack_grads(self, bufs: Sequence[jax.Array]) -> Pytree:
        """Per-bucket flat buffers -> pytree, each leaf keeping its
        buffer's dtype (the inverse of ``pack_grads``; rare host-facing
        path — the hot loop never unpacks gradients)."""
        return jax.tree_util.tree_unflatten(
            self.treedef, self._unpack_leaves(bufs, dtypes=None))

    def unpack_model(self, bufs: Sequence[jax.Array]) -> Pytree:
        """Per-bucket flat buffers -> pytree in the MODEL dtypes."""
        return jax.tree_util.tree_unflatten(
            self.treedef, self.model_leaves(bufs))

    def model_leaves(self, bufs: Sequence[Optional[jax.Array]]
                     ) -> List[Optional[jax.Array]]:
        """``unpack_model``'s leaves in leaf-index order; the leaves of
        a bucket whose buffer is None stay None."""
        return self._unpack_leaves(bufs,
                                   [b.model_dtype for b in self.buckets])

    # ---- optimizer-state packing ----------------------------------------
    # Generic rule covering every fused optimizer's state layout:
    #   * a state field whose leaves mirror the param shapes packs into
    #     per-bucket flat buffers (exp_avg, exp_avg_sq, momentum, sum);
    #   * a state field whose leaves are all scalars packs into one
    #     (num_segments,) vector per bucket (NovoGrad's per-tensor
    #     second moment), indexed by the bucket-local leaf ordinal;
    #   * a state field whose leaves are all the SAME small (H,) vector
    #     (and do NOT mirror the param shapes) stacks into one
    #     (num_segments, H) matrix per bucket, row per leaf — the fp8
    #     per-tensor amax-history slot.
    def _field_is_leaf_vectors(self, leaves) -> bool:
        """True for the row-stacked layout: every leaf a same-length
        1-D vector that is NOT this plan's own leaf shape (a params
        tree of uniform (H,) vectors keeps the flat pack — the two
        layouts would otherwise be write-ambiguous)."""
        shapes = {tuple(getattr(l, "shape", ())) for l in leaves}
        if len(shapes) != 1:
            return False
        (shape,) = shapes
        if len(shape) != 1:
            return False
        return any(s.shape != shape
                   for b in self.buckets for s in b.leaves)

    def pack_state_field(self, field: Pytree) -> List[jax.Array]:
        leaves = _leaf_arrays(field)
        if len(leaves) != self.n_leaves:
            raise ValueError("state field does not mirror the plan's tree")
        if all(getattr(l, "shape", ()) == () for l in leaves):
            return [jnp.stack([jnp.asarray(leaves[s.index], jnp.float32)
                               for s in b.leaves])
                    for b in self.buckets]
        if self._field_is_leaf_vectors(leaves):
            return [jnp.stack([jnp.asarray(leaves[s.index], jnp.float32)
                               for s in b.leaves])
                    for b in self.buckets]
        return self.pack(field)

    def unpack_state_field(self, bufs: Sequence[jax.Array]) -> Pytree:
        # Per-leaf-scalar layout iff every bucket's buffer is exactly
        # (num leaves,).  When that coincides with the flat layout
        # (every param leaf itself a scalar) the two agree elementwise,
        # so either unpack is correct.  State dtypes (f32 moments even
        # for bf16 work buffers) are preserved: no work-dtype cast here.
        # A 2-D (num leaves, H) buffer is the row-stacked per-leaf-
        # vector layout (fp8 amax history), unless every buffer has its
        # bucket's own shape: the pack of a field that mirrors the
        # params (a leaf-shaped bucket's buffer may be 2-D too).
        mirror = all(tuple(getattr(bufs[bi], "shape", ())) == b.shape
                     for bi, b in enumerate(self.buckets))
        if not mirror and all(getattr(bufs[bi], "ndim", None) == 2
                              and bufs[bi].shape[0] == len(b.leaves)
                              for bi, b in enumerate(self.buckets)):
            leaves: List[Optional[jax.Array]] = [None] * self.n_leaves
            for bi, b in enumerate(self.buckets):
                for j, s in enumerate(b.leaves):
                    leaves[s.index] = bufs[bi][j]
            return jax.tree_util.tree_unflatten(self.treedef, leaves)
        scalar = all(tuple(bufs[bi].shape) == (len(b.leaves),)
                     for bi, b in enumerate(self.buckets))
        flat = all(bufs[bi].size == b.size
                   for bi, b in enumerate(self.buckets))
        if scalar and not flat:
            leaves = [None] * self.n_leaves
            for bi, b in enumerate(self.buckets):
                for j, s in enumerate(b.leaves):
                    leaves[s.index] = bufs[bi][j]
            return jax.tree_util.tree_unflatten(self.treedef, leaves)
        return jax.tree_util.tree_unflatten(
            self.treedef, self._unpack_leaves(bufs, dtypes=None))

    # ---- segment metadata ------------------------------------------------
    def segment_sizes(self, bucket_index: int) -> Tuple[int, ...]:
        """One bucket's static segment boundaries: its leaves' element
        counts in buffer order (leaves are contiguous, so leaf ``j``
        starts where ``sizes[:j]`` end).  Python ints — the segmented
        LAMB/NovoGrad/fp8 kernels slice with them at trace time, and no
        bucket-sized index array exists in the program or as a
        constant of it (at BERT-Large's 334 M elements such a constant
        was 1.25 GB of program and minutes of compile)."""
        return tuple(s.size for s in self.buckets[bucket_index].leaves)

    def describe(self) -> List[dict]:
        """Human/bench-facing plan summary."""
        return [{"dtype": str(np.dtype(b.dtype)),
                 "model_dtype": str(np.dtype(b.model_dtype)),
                 "leaves": len(b.leaves), "elements": b.size,
                 "leaf_shaped": b.leaf_shaped}
                for b in self.buckets]

    # ---- layout (de)serialization ----------------------------------------
    def leaf_paths(self) -> List[str]:
        """``jax.tree_util.keystr`` path per leaf, in leaf-index order —
        the human-readable identity the checkpoint v2 header records so
        a restore onto a DIFFERENT tree fails with a named leaf, not a
        positional index."""
        dummy = jax.tree_util.tree_unflatten(
            self.treedef, list(range(self.n_leaves)))
        flat, _ = jax.tree_util.tree_flatten_with_path(dummy)
        paths: List[Optional[str]] = [None] * self.n_leaves
        for path, idx in flat:
            paths[idx] = jax.tree_util.keystr(path)
        return paths  # type: ignore[return-value]

    def layout(self) -> dict:
        """JSON-able static layout: leaf paths plus every bucket's
        dtypes, element count and per-leaf shape/offset table.  This is
        the checkpoint v2 header's ``plan`` record: enough to (a) slice
        a flat bucket buffer back into per-leaf arrays on the host with
        no device traffic, and (b) decide whether a restoring
        optimizer's own plan matches bit-for-bit (same doc ==> packed
        buffers can be adopted directly).  A leaf-shaped bucket's
        buffer has the same bytes, row-major, as its flat one, so the
        record holds for both: the payload is stored flat."""
        return {
            "paths": self.leaf_paths(),
            "buckets": [
                {"dtype": np.dtype(b.dtype).name,
                 "model_dtype": np.dtype(b.model_dtype).name,
                 "size": b.size,
                 "leaves": [{"index": s.index, "shape": list(s.shape),
                             "offset": s.offset} for s in b.leaves]}
                for b in self.buckets],
        }


# ---- cached standalone plans ----------------------------------------------
# The fused optimizers own their plan; everything else on the flat
# gradient pipeline (FlatGradPipeline without an optimizer, the bucketed
# Reducer, packed clip_grad) needs one too — built ONCE per distinct
# tree layout, keyed on (treedef, leaf shape/dtype signature), so
# repeated calls (including from inside a jit trace) reuse the same
# static offsets instead of recomputing the layout.
_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 64


def _leaf_multi_device(l):
    """True/False for concrete arrays, None for tracers (sharding
    unknown at trace time) — part of the cache key so a plan built for
    single-device arrays is never reused for same-shaped multi-device
    ones (from_tree declines those) or vice versa."""
    try:
        return len(l.sharding.device_set) > 1
    except AttributeError:
        return None


def cached_plan(tree: Pytree, model: Optional[Pytree] = None,
                max_bucket_bytes: Optional[int] = None
                ) -> Optional[BucketPlan]:
    """Memoized ``BucketPlan.from_tree`` (grad-only pack entry point).

    Works on concrete arrays and tracers alike.  Returns None exactly
    when ``from_tree`` would (non-float or multi-device leaves); the
    key carries shapes, dtypes, device placement AND the chunking cap,
    so the memo never bypasses from_tree's multi-device guard and a
    chunked plan is never served where a monolithic one was asked."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = tuple((tuple(getattr(l, "shape", ())),
                 jnp.dtype(getattr(l, "dtype", jnp.float32)).name,
                 _leaf_multi_device(l))
                for l in leaves if hasattr(l, "dtype"))
    if len(sig) != len(leaves):
        return None
    if model is not None:
        sig += tuple(jnp.dtype(l.dtype).name
                     for l in jax.tree_util.tree_leaves(model))
    key = (treedef, sig, max_bucket_bytes)
    if key not in _PLAN_CACHE:
        if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
            _PLAN_CACHE.clear()
        _PLAN_CACHE[key] = BucketPlan.from_tree(
            tree, model, max_bucket_bytes=max_bucket_bytes)
    return _PLAN_CACHE[key]
