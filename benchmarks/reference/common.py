"""What the plain references share: the control's operand rounding and
the norms by leaf that the comparison reads.  Nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rounder(precision: str):
    """Operand rounding for the control (``bf16``: 8 exponent and 7
    mantissa bits; ``fp8``: e4m3, 4 and 3, under a per-tensor scale
    that puts the largest magnitude at 240, the format's largest);
    identity for the float32 reference.  ``lax.reduce_precision``, not a
    pair of casts: XLA may drop a cast down and up again as excess
    precision it is allowed to keep, and did on the chip.  The gradient
    passes straight through the rounding."""
    if precision == "f32":
        return lambda x: x
    if precision == "bf16":
        def q(x):
            return jax.lax.reduce_precision(x, 8, 7)
    elif precision == "fp8":
        def q(x):
            scale = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
            return jax.lax.reduce_precision(x / scale, 4, 3) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda x: x + jax.lax.stop_gradient(q(x) - x)


@jax.jit
def norms(tree):
    return jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)


@jax.jit
def diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b)


def as_floats(tree, scale: float = 1.0):
    return jax.tree_util.tree_map(lambda x: float(x) * scale,
                                  jax.device_get(tree))


def unzip(treedef, outs, n):
    """Leaf-wise tuples -> n trees."""
    return tuple(treedef.unflatten([o[i] for o in outs]) for i in range(n))
