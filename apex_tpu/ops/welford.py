"""Welford batch-statistics kernels (reference: csrc/syncbn.cpp +
csrc/welford.cu, SURVEY.md §2.4).

The reference computes per-GPU Welford mean/var, all-gathers the partial
(mean, var, count) triples, and merges them with Chan's parallel combine.
The TPU design is identical in structure: a Pallas kernel produces the
LOCAL (per-shard) triple with one pass over (rows, C) data, and
``welford_combine`` merges triples — either across grid blocks (inside
the kernel) or across mesh devices (via all_gather in
apex_tpu.parallel.sync_batchnorm).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import interpret_mode, op_enabled

LANE = 128
_BLOCK_ROWS = 256


def welford_combine(n_a, mean_a, m2_a, n_b, mean_b, m2_b):
    """Chan's parallel combine of two (count, mean, M2) triples.

    Shapes broadcast; counts are scalars or (1, C).  Guarded for empty
    partitions (n == 0).
    """
    n = n_a + n_b
    safe_n = jnp.where(n > 0, n, 1.0)
    delta = mean_b - mean_a
    mean = mean_a + delta * (n_b / safe_n)
    m2 = m2_a + m2_b + delta * delta * (n_a * n_b / safe_n)
    return n, mean, m2


def _welford_kernel(total_rows, x_ref, cnt_ref, mean_ref, m2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        # SMEM refs take SCALAR stores under Mosaic (interpret mode is
        # laxer — this was round 2's hardware-validation catch)
        cnt_ref[0, 0] = jnp.float32(0.0)
        mean_ref[...] = jnp.zeros_like(mean_ref)
        m2_ref[...] = jnp.zeros_like(m2_ref)

    x = x_ref[...].astype(jnp.float32)
    br = x.shape[0]
    row_ids = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    valid = (row_ids < total_rows).astype(jnp.float32)
    n_b = jnp.sum(valid)
    safe_nb = jnp.maximum(n_b, 1.0)
    xm = x * valid
    mean_b = jnp.sum(xm, axis=0, keepdims=True) / safe_nb
    m2_b = jnp.sum(valid * (x - mean_b) ** 2, axis=0, keepdims=True)
    n, mean, m2 = welford_combine(
        cnt_ref[0, 0], mean_ref[...], m2_ref[...], n_b, mean_b, m2_b)
    cnt_ref[0, 0] = n
    mean_ref[...] = mean
    m2_ref[...] = m2


@jax.named_scope("apex_welford")
def welford_mean_var(x2d: jax.Array) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """Local Welford stats of an (N, C) array, reduced over N.

    Returns (mean (C,), biased var (C,), count scalar) — the reference's
    syncbn.welford_mean_var contract.  C must be a multiple of 128 for
    the Pallas path; otherwise the XLA fallback runs.
    """
    n, c = x2d.shape
    if not (op_enabled("welford") and c % LANE == 0):
        return welford_mean_var_ref(x2d)
    rows = (n + _BLOCK_ROWS - 1) // _BLOCK_ROWS * _BLOCK_ROWS
    xp = jnp.pad(x2d, ((0, rows - n), (0, 0)))
    cnt, mean, m2 = pl.pallas_call(
        functools.partial(_welford_kernel, n),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="apex_syncbn_welford",
    )(xp)
    count = cnt[0, 0]
    var = m2[0] / jnp.maximum(count, 1.0)
    return mean[0], var, count


@jax.named_scope("apex_welford")
def welford_mean_var_ref(x2d: jax.Array):
    xf = x2d.astype(jnp.float32)
    n = xf.shape[0]
    mean = jnp.mean(xf, axis=0)
    var = jnp.mean((xf - mean) ** 2, axis=0)
    return mean, var, jnp.float32(n)
