"""Fused gradient clipping (reference: apex/contrib/clip_grad/clip_grad.py,
SURVEY.md §2.3 — `clip_grad_norm_` over amp_C.multi_tensor_l2norm +
multi_tensor_scale).

The reference's win is ONE l2norm kernel over all grads and ONE scale
kernel, instead of per-tensor launches.  TPU-native: ravel the grad
pytree once, take the global norm with the Pallas flat_l2norm kernel,
scale with flat_scale — two fused passes, no per-leaf work.  JAX arrays
are immutable so the "in-place" entry point returns the clipped tree.

Packed gradients (the flat AMP pipeline's per-bucket buffer list, see
amp/flat_pipeline.py) delegate straight to the fused per-bucket path —
no ravel_pytree, no re-concatenation: one l2norm per bucket rss-combined
into the global norm, one scale per bucket, buffers in / buffers out.
Inside the full pipeline even this is unnecessary — ``FlatGrads.clip_coef``
folds into the optimizer kernels and the scale pass never runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from apex_tpu.ops.multi_tensor import flat_l2norm, flat_scale


def _is_packed(grads) -> bool:
    """A per-bucket buffer list (BucketPlan layout: 1-D buffers, and a
    one-leaf bucket's in its leaf's shape): a PLAIN list/tuple of float
    arrays of one axis or more — exact types only, so NamedTuple
    pytrees (whose constructors take positional fields and would not
    survive the packed-path rebuild) keep the ravel_pytree path.
    Clipping by GLOBAL norm is layout-invariant, so treating a genuine
    list-of-arrays pytree this way returns the same values — only the
    (faster) code path differs."""
    if type(grads) not in (list, tuple) or not grads:
        return False
    return all(getattr(g, "ndim", 0) >= 1
               and hasattr(g, "dtype")
               and jnp.issubdtype(g.dtype, jnp.floating) for g in grads)


def _total_norm(flats, norm_type):
    """Global norm over a list of flat buffers, f32 accumulation."""
    if norm_type == 2.0:
        return jnp.sqrt(sum(flat_l2norm(f) ** 2 for f in flats))
    if norm_type == float("inf"):
        return jnp.max(jnp.stack(
            [jnp.max(jnp.abs(f.astype(jnp.float32))) for f in flats]))
    acc = sum(jnp.sum(jnp.abs(f.astype(jnp.float32)) ** norm_type)
              for f in flats)
    return acc ** (1.0 / norm_type)


def clip_grad_norm(grads, max_norm, norm_type=2.0, eps=1e-6):
    """Clip a grad pytree — or a packed per-bucket buffer list — to
    global norm max_norm.

    Returns (clipped_grads, total_norm), clipped in the input's layout
    (packed in -> packed out).  norm_type 2.0 uses the fused Pallas
    l2norm; other norms (incl. inf) go through XLA.
    """
    if _is_packed(grads):
        total_norm = _total_norm(list(grads), norm_type)
        scale = jnp.minimum(max_norm / (total_norm + eps), 1.0)
        s = scale.astype(jnp.float32)
        # preserve the input container (tuple in -> tuple out): a
        # tuple-of-vectors PYTREE taking this path must round-trip its
        # structure for the caller's tree_map against params
        return (type(grads)(flat_scale(g, s)[0] for g in grads),
                total_norm)
    flat, unravel = ravel_pytree(grads)
    total_norm = _total_norm([flat], norm_type)
    scale = jnp.minimum(max_norm / (total_norm + eps), 1.0)
    clipped, _ = flat_scale(flat, scale.astype(jnp.float32))
    return unravel(clipped.astype(flat.dtype)), total_norm


def clip_grad_norm_(grads, max_norm, norm_type=2.0):
    """Reference-shaped entry point (same name incl. trailing underscore).

    Reference returns the pre-clip total norm; here the clipped tree comes
    too since mutation is impossible: (clipped_grads, total_norm)."""
    return clip_grad_norm(grads, max_norm, norm_type)
