"""Grouped matrix products over rows sorted by group, as Pallas kernels.

`grouped_dot(x, w, sizes)`: the rows of x [A, K] come sorted by group,
sizes[g] of them for group g, and each is multiplied by its group's
w[g] [K, N]; rows past sum(sizes) belong to no group and come out zero,
with zero cotangents.  It is the expert layer's product (`ops/moe.py`): the
rows are the assignments sorted by expert, the groups the experts held.

Two kernels, both over a grid of VISITS: a visit is one (group, row tile)
pair where the group has rows in the tile, so a tile that two groups share
is visited twice and a tile past the rows held not at all.  The visits are
made on the device from `sizes` and reach the kernel by scalar prefetch;
their number is the grid's length, so no tile of zeros is multiplied.

- `moe.gmm`: x @ w[g] (forward) or x @ w[g]^T (the input's cotangent), the
  contracted dimension whole in one block, so a group's block of w is
  fetched once however many tiles the group spans.  Each visit writes the
  rows of its tile that its group owns.
- `moe.tgmm`: x_g^T dy_g for every group (the weights' cotangent), summed
  in float32 over the group's visits; a group with no rows is visited once
  and writes zeros.

Operands are multiplied in their own type with float32 accumulation and
the result is rounded to the operands' type, as `lax.ragged_dot` gives.
The entry points are jitted on their own, with only tiling and flags
static: a step that calls the product at many sites (a layer's three
matrices forward and backward, the recompute, both programs of
`moe._compact_or_full`, unrolled layers) traces and lowers each distinct
shape once, and XLA inlines the calls.  Off the TPU the same kernels run in
Pallas's interpreter (`_launch`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.names import kernel
from .bfp_pallas import _is_tpu

# Rows a tile: the most a tile may have, and the fewest a tile of a 16-bit
# operand may have (its native tile is 16 x 128).
ROW_TILE, MIN_ROWS = 256, 16
# Columns a block of w (or of the weights' cotangent) may have at most, and
# rows a block of the weights' cotangent may have at most.
LANE_TILE, TGMM_K_TILE = 1024, 512
VMEM_CAP = 96 << 20


def _row_tile(rows: int) -> int:
    """The tile of rows: ROW_TILE, or the largest power of two of at least
    MIN_ROWS that divides `rows`, or all of them."""
    t = ROW_TILE
    while t >= MIN_ROWS:
        if rows % t == 0:
            return t
        t //= 2
    return rows


def _lane_tile(dim: int, cap: int = LANE_TILE) -> int:
    """The widest multiple of 128 that divides `dim` and is at most `cap`;
    `dim` itself where none does (a block may always span a dimension)."""
    for t in range(cap - cap % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _vmem_limit(*buffers: int) -> int:
    """Fast memory for a kernel whose blocks take `buffers` bytes (double
    buffered by the pipeline), with room for Mosaic's own temporaries."""
    return min(VMEM_CAP, max(32 << 20, 3 * sum(buffers)))


def _sum(a: jax.Array, axis: int) -> jax.Array:
    return lax.reduce(a, np.int32(0), lax.add, (axis,))


def _visits(sizes: jax.Array, rows: int, tm: int, empty_too: bool):
    """Which group and which row tile each visit works on.

    Returns (group [V], tile [V], bounds [G+1], count): V = rows/tm + G is
    the most visits any `sizes` needs; the first `count` are real, the rest
    repeat the last so that nothing moves.  bounds[g]:bounds[g+1] are group
    g's rows (cut at `rows`).  Where `empty_too`, a group with no rows is
    visited once, on the tile where it would start.  Written in lax
    primitives on [V, G] masks: it is traced for every shape of the
    kernels, and jax.numpy's jitted helpers cost more to trace than the
    arithmetic does."""
    groups, tiles = sizes.shape[0], rows // tm
    upto = np.tril(np.ones((groups, groups), bool))        # [g, h]: h <= g

    def cumsum(a):
        return _sum(lax.select(upto, lax.broadcast(a, (groups,)),
                               lax.full_like(upto, 0, a.dtype)), 1)

    ends = lax.min(cumsum(sizes), rows)
    starts = lax.concatenate([lax.full((1,), 0, jnp.int32),
                              lax.slice(ends, (0,), (groups - 1,))], 0)
    first = lax.min(lax.div(starts, tm), tiles - 1)
    n = lax.select(lax.gt(ends, starts),
                   lax.sub(lax.div(lax.sub(ends, 1), tm), lax.sub(first, 1)),
                   lax.full_like(sizes, 1 if empty_too else 0))
    at = lax.sub(cumsum(n), n)                             # g's first visit
    count = lax.max(_sum(n, 0), np.int32(1))
    v = lax.min(lax.iota(jnp.int32, tiles + groups), lax.sub(count, 1))
    vv = lax.broadcast_in_dim(v, (v.shape[0], groups), (0,))
    mine = lax.bitwise_and(                                # [V, G], one-hot
        lax.ge(vv, lax.broadcast(at, (v.shape[0],))),
        lax.lt(vv, lax.broadcast(lax.add(at, n), (v.shape[0],))))

    def pick(a):                                           # a[group of v]
        return _sum(lax.select(mine, lax.broadcast(a, (v.shape[0],)),
                               lax.full_like(mine, 0, a.dtype)), 1)

    group = pick(lax.iota(jnp.int32, groups))
    tile = lax.min(lax.add(pick(lax.sub(first, at)), v), tiles - 1)
    bounds = lax.concatenate([lax.full((1,), 0, jnp.int32), ends], 0)
    return group, tile, bounds, count


def _owned(bounds_ref, g, t, tm, shape):
    """Which rows of tile t (a mask of `shape`, rows first) group g owns."""
    row = t * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])


def _gmm_kernel(group_ref, tile_ref, bounds_ref, x_ref, w_ref, o_ref, *,
                tm, transpose):
    v = pl.program_id(1)
    g, t = group_ref[v], tile_ref[v]
    contract = ((1,), (1,)) if transpose else ((1,), (0,))
    y = lax.dot_general(x_ref[...], w_ref[...], (contract, ((), ())),
                        preferred_element_type=jnp.float32)
    mine = _owned(bounds_ref, g, t, tm, y.shape)
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _tgmm_kernel(group_ref, tile_ref, bounds_ref, x_ref, dy_ref, o_ref,
                 acc_ref, *, tm):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g, t = group_ref[v], tile_ref[v]

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mine = _owned(bounds_ref, g, t, tm, (tm, 1))
    x = jnp.where(mine, x_ref[...], jnp.zeros((), x_ref.dtype))
    dy = jnp.where(mine, dy_ref[...], jnp.zeros((), dy_ref.dtype))
    acc_ref[...] += lax.dot_general(x, dy, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != g))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _vma(*arrays) -> frozenset:
    """The manual mesh axes any of `arrays` varies over (inside shard_map
    the kernel's result varies over them too)."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _launch(call, interpret, *args):
    """call(*args), an array or a tuple.  Pallas's interpreter evaluates a
    kernel apart from jax's record of the manual mesh axes a value varies
    over and refuses there (inside shard_map) a scalar prefetch or a block
    that meets a varying value, so it is handed every rank's operands
    gathered, runs each rank's in turn and each rank keeps its own result."""
    axes = tuple(sorted(_vma(*args)))
    if not interpret or not axes:
        return call(*args)
    me, ranks = lax.axis_index(axes), lax.psum(1, axes)

    def whole(a):          # every rank's `a`, the same on every rank
        a = lax.pcast(a, tuple(ax for ax in axes
                               if ax not in jax.typeof(a).vma), to="varying")
        return lax.psum(jnp.zeros((ranks,) + a.shape, a.dtype).at[me].set(a),
                        axes)

    every = [whole(a) for a in args]
    return jax.tree_util.tree_map(lambda *o: jnp.stack(o)[me], *[
        call(*(a[r] for a in every)) for r in range(ranks)])


def _held_rows(y: jax.Array, bounds: jax.Array) -> jax.Array:
    """y with its rows past the last group's zeroed."""
    live = lax.broadcasted_iota(jnp.int32, y.shape, 0) < bounds[-1]
    return lax.select(live, y, lax.full_like(y, 0))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmm(x, w, sizes, transpose, interpret):
    """x [A, K] @ w[g] per group (w [G, K, N]), or @ w[g]^T where
    `transpose` (w [G, N, K]): [A, N], rows past sum(sizes) zero."""
    rows, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    tm, tn = _row_tile(rows), _lane_tile(n)
    item = x.dtype.itemsize
    if transpose:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, v, gr, ti, b: (
            gr[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, v, gr, ti, b: (
            gr[v], 0, j))

    def call(sizes, x, w):
        group, tile, bounds, count = _visits(sizes, rows, tm, False)
        y = pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm, transpose=transpose),
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype,
                                           vma=_vma(sizes, x, w)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n // tn, count),
                in_specs=[pl.BlockSpec((tm, k), lambda j, v, gr, ti, b: (
                    ti[v], 0)), w_spec],
                out_specs=pl.BlockSpec((tm, tn), lambda j, v, gr, ti, b: (
                    ti[v], j))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(
                    2 * tm * k * item, 2 * k * tn * item,
                    2 * tm * tn * item, tm * tn * 4)),
            cost_estimate=pl.CostEstimate(
                flops=2 * rows * k * n, transcendentals=0,
                bytes_accessed=(rows * k + w.size + rows * n) * item),
            interpret=interpret,
            **kernel("moe.gmm", rhs="transposed" if transpose else None),
        )(group, tile, bounds, x, w)
        return _held_rows(y, bounds)

    return _launch(call, interpret, sizes, x, w)


@functools.partial(jax.jit, static_argnums=(3,))
def _tgmm(x, dy, sizes, interpret):
    """x_g^T dy_g for every group g, x [A, K] and dy [A, N] sorted as
    `sizes` says: [G, K, N] in x's type, zeros for a group with no rows."""
    rows, k = x.shape
    n = dy.shape[1]
    groups = sizes.shape[0]
    tm, tk, tn = _row_tile(rows), _lane_tile(k, TGMM_K_TILE), _lane_tile(n)
    item = x.dtype.itemsize

    def call(sizes, x, dy):
        group, tile, bounds, count = _visits(sizes, rows, tm, True)
        return pl.pallas_call(
            functools.partial(_tgmm_kernel, tm=tm),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), x.dtype,
                                           vma=_vma(sizes, x, dy)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(k // tk, n // tn, count),
                in_specs=[
                    pl.BlockSpec((tm, tk), lambda i, j, v, gr, ti, b: (
                        ti[v], i)),
                    pl.BlockSpec((tm, tn), lambda i, j, v, gr, ti, b: (
                        ti[v], j))],
                out_specs=pl.BlockSpec(
                    (None, tk, tn), lambda i, j, v, gr, ti, b: (gr[v], i, j)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(
                    2 * tm * tk * item, 2 * tm * tn * item,
                    2 * tk * tn * item, tk * tn * 4)),
            cost_estimate=pl.CostEstimate(
                flops=2 * rows * k * n, transcendentals=0,
                bytes_accessed=(rows * (k + n) + groups * k * n) * item),
            interpret=interpret,
            **kernel("moe.tgmm"),
        )(group, tile, bounds, x, dy)

    return _launch(call, interpret, sizes, x, dy)


def _no_cotangent(ints: jax.Array):
    return np.zeros(ints.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_dot(x, w, sizes, interpret):
    return _gmm(x, w, sizes, False, interpret)


def _grouped_dot_fwd(x, w, sizes, interpret):
    return _gmm(x, w, sizes, False, interpret), (x, w, sizes)


def _grouped_dot_bwd(interpret, res, dy):
    x, w, sizes = res
    return (_gmm(dy, w, sizes, True, interpret),
            _tgmm(x, dy, sizes, interpret), _no_cotangent(sizes))


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def grouped_dot(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """Rows of x [A, K], sorted by group, times their group's w [G, K, N]:
    [A, N] in x's type, the first sizes[0] rows by w[0], the next sizes[1]
    by w[1], ...; rows past sum(sizes) come out zero and take no part in
    either cotangent."""
    if x.dtype != w.dtype:
        raise TypeError(f"grouped_dot multiplies operands of one type, not "
                        f"{x.dtype} and {w.dtype}")
    # traced under the mesh that the backward pass sets from the cotangent
    # (the operands' own), so that a forward here and the recompute in a
    # backward share one trace and one lowering of each shape
    with jax.sharding.use_abstract_mesh(jax.typeof(x).sharding.mesh):
        return _grouped_dot(x, w, sizes.astype(jnp.int32), not _is_tpu())
