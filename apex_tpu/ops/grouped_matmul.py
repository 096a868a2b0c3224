"""Grouped matrix products over rows sorted by group: the dropless expert
layer's (``transformer/moe.py:dropless_moe``).

    grouped_matmul(lhs (m, k), rhs (G, k, n), counts (G,)) -> (m, n)
        out[r] = lhs[r] @ rhs[g]     for the rows r of group g

The groups' rows are consecutive, group 0 first, ``counts[g]`` rows
each: ``sum(counts)`` rows in use at the front of the buffer.  Rows of
the output past them are not written, and no caller may read them.  The
gradient runs two more kernels: dlhs = the product with each rhs[g]
transposed, and drhs[g] = lhs_g^T dout_g over the group's rows.  Three
Pallas kernels, ``apex_gmm_fwd``, ``apex_gmm_dlhs``, ``apex_gmm_dw``.

A grid step is one VISIT: a (row tile, group) pair that holds rows of
the group.  The visits are listed on the device from ``counts``
(``_visits``) and reach the index maps and the bodies as prefetched
scalars; their number is the grid's trip count, so a call costs the rows
routed to it, and a row past ``sum(counts)`` is never visited.  A row
tile that a group boundary crosses is visited once by each of its
groups, one after the other, and each visit writes its group's rows of
the output tile, which stays in VMEM in between (``apex_gmm_dw``: each
visit adds its group's rows to the group's accumulator).

The tiles follow from the call (``_tiles``):

- the contraction is one block, whole, and the output columns the
  widest lane-multiple divisor whose double-buffered blocks fit
  ``_VMEM_BUDGET``, within the compiler's default scoped VMEM; where
  none fits (a width with no lane-multiple divisor, as 1856) the
  kernel asks for the VMEM its blocks need.  A kernel's VMEM limit is
  kept out of the compiler's own prefetching for the whole program, so
  a larger one slows the program's other operations;
- the grid runs the visits inside the column tiles, so consecutive
  visits of one group keep the block index of its weights and Pallas
  does not fetch them again: a call reads each held expert's weights
  once a column tile;
- the row tile is ``ROW_TILE`` = 128 rows: a group boundary wastes at
  most part of one small tile.

Operands keep their dtype; products accumulate in float32 (``precision``
as the caller's dot would take it) and are written in the operands'
dtype.  Each kernel program is lowered once a program
(``_dispatch.lowered_once``) under the scope ``apex_moe/experts``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import lowered_once

SCOPE = "apex_moe/experts"
ROW_TILE = 128               # rows a visit
_VMEM_BUDGET = 12 << 20      # the blocks, within the v5e's default 16 MiB
_VMEM_SPARE = 16 << 20       # Mosaic's own temporaries beside larger ones
F32 = jnp.float32


def _divisors(n: int):
    """n itself, then its lane-multiple divisors, widest first."""
    return [n] + [d for d in range(n - n % 128, 0, -128)
                  if d != n and n % d == 0]


def _tiles(tm, k, n, itemsize, acc):
    """(tk, tn, VMEM limit): the widest blocks that fit ``_VMEM_BUDGET``
    double-buffered, under the compiler's default limit (None), else the
    narrowest, under a limit raised to what they need.  A product takes
    the whole contraction k: blocks lhs (tm, k), rhs (k, tn), output
    (tm, tn) and its float32 product.  ``apex_gmm_dw`` (``acc``):
    operand blocks (tm, tk) and (tm, tn), an output block (tk, tn) and
    its float32 accumulator."""
    for tk in (_divisors(k) if acc else [k]):
        for tn in _divisors(n):
            if acc:
                need = 2 * (tm * tk + tm * tn + tk * tn) * itemsize \
                    + 2 * tk * tn * 4
            else:
                need = 2 * (tm * k + k * tn + tm * tn) * itemsize \
                    + tm * tn * 4
            if need <= _VMEM_BUDGET:
                return tk, tn, None
    return tk, tn, need + _VMEM_SPARE


def _params(limit, dims):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * dims, vmem_limit_bytes=limit)


def _visits(counts, rows, tm, with_empty):
    """The grid's visits from the group sizes: (group (V,), row tile
    (V,), offsets (G + 1,) the first row of each group and the end, the
    number of visits), V = row tiles + G - 1 the most there can be.
    Visits go group by group and, in a group, tile by tile; an empty
    group has one visit (every row masked) where ``with_empty``, so that
    its part of ``apex_gmm_dw`` is written, and none otherwise."""
    g = counts.shape[0]
    tiles_m = rows // tm
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = jnp.minimum(starts // tm, tiles_m - 1)
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1,
                      1 if with_empty else 0).astype(jnp.int32)
    done = jnp.cumsum(tiles)            # visits up to each group's last
    at = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    # the group of each visit by counting, not ``jnp.repeat`` (a scatter)
    group = jnp.minimum(jnp.sum(done[None, :] <= at[:, None], axis=1,
                                dtype=jnp.int32), g - 1)
    tile = jnp.minimum(first[group] + at - (done - tiles)[group],
                       tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32)
    return group, tile, offsets, done[-1]


def _rows(v, group, tile, offsets, tm):
    """Visit v's rows of its group: ((tm, 1) which rows of the tile are
    the group's, whether all are)."""
    g, start = group[v], tile[v] * tm
    row = start + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return ((row >= offsets[g]) & (row < offsets[g + 1]),
            (start >= offsets[g]) & (start + tm <= offsets[g + 1]))


def _product_kernel(group, tile, offsets, lhs, rhs, out, *, tm, transpose,
                    precision):
    v = pl.program_id(1)
    prod = jax.lax.dot_general(
        lhs[...], rhs[...], (((1,), (1 if transpose else 0,)), ((), ())),
        preferred_element_type=F32, precision=precision).astype(out.dtype)
    mine, whole = _rows(v, group, tile, offsets, tm)

    @pl.when(whole)
    def _():
        out[...] = prod

    @pl.when(jnp.logical_not(whole))
    def _():
        # the tile's first visit starts it from zeros: the rows of no
        # group (past the rows in use) are then zeros, not what VMEM
        # held before
        first = (v == 0) | (tile[jnp.maximum(v - 1, 0)] != tile[v])
        kept = jnp.where(first, jnp.zeros(out.shape, out.dtype), out[...])
        out[...] = jnp.where(mine, prod, kept)


def _dw_kernel(group, tile, offsets, lhs, dout, out, acc, *, tm, precision):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def add(a, b):
        acc[...] += jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())), preferred_element_type=F32,
            precision=precision)
    mine, whole = _rows(v, group, tile, offsets, tm)

    @pl.when(whole)
    def _():
        add(lhs[...], dout[...])

    # a tile shared with another group, or with rows of none, is masked
    # by select, not by a product: those rows may hold anything, NaN too
    @pl.when(jnp.logical_not(whole))
    def _():
        add(jnp.where(mine, lhs[...], 0), jnp.where(mine, dout[...], 0))

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _padded(x, rows):
    return x if x.shape[0] == rows else jnp.pad(
        x, ((0, rows - x.shape[0]), (0, 0)))


@lowered_once(SCOPE, static_argnums=(0, 1))
def _product(transpose, precision, lhs, rhs, counts, *, interpret):
    """lhs (m, k) @ rhs[g] (k, n), or @ rhs[g]^T for rhs (G, n, k) where
    ``transpose``, for the rows of each group; lhs' dtype out."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose else rhs.shape[2]
    tm = ROW_TILE
    rows = -(-m // tm) * tm
    _, tn, limit = _tiles(tm, k, n, lhs.dtype.itemsize, False)
    group, tile, offsets, visits = _visits(counts, rows, tm, False)
    if transpose:
        rhs_spec = pl.BlockSpec((None, tn, k),
                                lambda j, v, g, t, o: (g[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn),
                                lambda j, v, g, t, o: (g[v], 0, j))
    out = pl.pallas_call(
        functools.partial(_product_kernel, tm=tm, transpose=transpose,
                          precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, g, t, o: (t[v], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, o: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=_params(limit, 2), interpret=interpret,
        name="apex_gmm_dlhs" if transpose else "apex_gmm_fwd",
    )(group, tile, offsets, _padded(lhs, rows), rhs)
    return out[:m]


@lowered_once(SCOPE, static_argnums=(0,))
def _dw(precision, lhs, dout, counts, *, interpret):
    """drhs[g] = lhs_g^T dout_g (G, k, n) over each group's rows, lhs'
    dtype; zeros for an empty group."""
    m, k = lhs.shape
    n = dout.shape[1]
    tm = ROW_TILE
    rows = -(-m // tm) * tm
    tk, tn, limit = _tiles(tm, k, n, lhs.dtype.itemsize, True)
    group, tile, offsets, visits = _visits(counts, rows, tm, True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, v, g, t, o: (t[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, g, t, o: (t[v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, v, g, t, o: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), F32)]),
        out_shape=jax.ShapeDtypeStruct((counts.shape[0], k, n), lhs.dtype),
        compiler_params=_params(limit, 3), interpret=interpret,
        name="apex_gmm_dw",
    )(group, tile, offsets, _padded(lhs, rows), _padded(dout, rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, counts, precision):
    # the forward rule is traced under an empty abstract mesh, the
    # primal under none: the same context makes them one trace
    with jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh((), ())):
        return _product(False, precision, lhs, rhs, counts)


def _grouped_fwd(lhs, rhs, counts, precision):
    return (_product(False, precision, lhs, rhs, counts),
            (lhs, rhs, counts))


def _grouped_bwd(precision, res, dout):
    lhs, rhs, counts = res
    return (_product(True, precision, dout, rhs, counts),
            _dw(precision, lhs, dout, counts), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, counts, *, precision=None):
    """lhs (m, k) rows sorted by group, rhs (G, k, n), counts (G,) int32
    with sum(counts) <= m -> (m, n) in the operands' dtype: out[r] =
    lhs[r] @ rhs[g] for the rows r of group g; rows past sum(counts)
    undefined.  Differentiable in lhs and rhs."""
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul: operands of {lhs.dtype} and "
                         f"{rhs.dtype}")
    return _grouped(lhs, rhs, counts.astype(jnp.int32), precision)
