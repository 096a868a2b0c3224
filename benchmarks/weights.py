"""Weights from the seed, made by the benchmark and handed to the program
and to the plain reference alike: neither takes anything the other made.

A reference module describes its parameters as a nested dict whose
leaves are ``(shape, init)`` with ``init`` one of ``("normal", std)``,
``("ones",)``, ``("zeros",)``; ``make`` fills the tree on the device in
ONE jitted call, float32 (amp makes its own half copies from them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any non-negative whole number: ``jax.random.key``
    keeps 32 bits, the rest is folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _fill(spec, key):
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init[0] == "normal":
            out.append(init[1] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
        elif init[0] == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif init[0] == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            raise ValueError(f"unknown init {init!r}")
    return jax.tree_util.tree_unflatten(treedef, out)


def maker(spec):
    """``key -> tree`` for ``spec``, traceable (the drivers call it
    inside their own jits to regenerate the initial weights)."""
    return functools.partial(_fill, spec)


def make(spec, seed: int):
    return jax.jit(maker(spec))(seed_key(seed))
