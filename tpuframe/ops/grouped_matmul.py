"""Grouped matrix product: rows sorted by group, one weight a group.

``grouped_matmul(rows, weights, group_sizes)`` multiplies the first
``group_sizes[0]`` rows of ``rows`` by ``weights[0]``, the next
``group_sizes[1]`` by ``weights[1]`` and so on; rows past
``sum(group_sizes)`` come out zero, in the result and in both gradients.
It is the expert matmul of a
mixture-of-experts layer that drops no token: assignments are sorted by
expert into a buffer with a slot for every (token, choice) pair, the
group sizes are whatever the router made them, and the rows behind the
real assignments (and the assignments to experts held elsewhere) belong
to no group.

On the TPU the three products are Pallas kernels of this module, driven
by the group sizes through a plan in scalar memory (the design to read
beside them is ``jax.experimental.pallas.ops.tpu.megablox``):

- ``tpuframe_grouped_fwd`` (rows x weights) and ``tpuframe_grouped_drows``
  (cotangent x weights transposed: the contraction runs over the weight's
  LAST axis inside the kernel, so the backward reads the forward's
  weights as they lie) walk the row tiles group by group, ``_TILE_ROWS``
  rows a tile.  A tile two groups share is visited once by each, the
  other group's rows masked by a select on the tile, and a visit that
  touches one half of its tile multiplies that half alone
  (:func:`row_tile` rows: where a group ends the product follows it to
  half a tile); a group's weight block stays in VMEM across its tiles.
  Tiles past the groups are written as zeros with no product.
- ``tpuframe_grouped_dweights`` (rows transposed x cotangent, group by
  group) walks the same tiles and halves, accumulates a group's in
  float32 in VMEM and writes its block once; an empty group's block is
  written as zeros.

Same arithmetic as ``jax.lax.ragged_dot``: the operands' dtype on the
MXU, float32 accumulation, one rounding to the result's dtype.  Block
sizes follow the shapes (:func:`_blocks`): the contraction whole, the
weight's other axis whole where a block fits ``_BLOCK_BYTES``, else the
most whole lanes that divide it.  :func:`tiles_visited` counts the tile
visits from the group sizes, for the ``moe/rows_computed`` counter.

Wherever the kernels do not run (a CPU, a process of several devices
whose weights GSPMD may have split, a manual region, a shape no block
fits, a caller's ``kernels=False``) the product is
``jax.lax.ragged_dot``, whose own TPU kernel works in tiles of
:data:`RAGGED_TILE_ROWS` and leaves the tiles it does not visit
unwritten, so that path zeroes what lies past the groups with selects of
its own.  One ``ops/kernel_verdict`` event a distinct decision says which
form ran and the tile it took.  :func:`grouped_matmul_grads` is the two
gradients' products alone, for a caller that writes its own backward
pass from the arrays it kept.

:func:`grouped_matmul_reference` is the oracle: every row against every
group's weight, masked by membership.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuframe.ops.dispatch import inside_shard_map, resolve_interpret
from tpuframe.ops.registry import shape_class

__all__ = ["grouped_matmul", "grouped_matmul_grads", "grouped_matmul_reference",
           "row_tile", "tiles_visited", "RAGGED_TILE_ROWS"]

#: rows of one tile of XLA's ragged-dot kernel on the TPU, read off the
#: module it compiles for a v5e (49152 rows, 8 groups: 103 tile slots)
RAGGED_TILE_ROWS = 512
#: rows of one tile of this module's kernels
_TILE_ROWS = 256
_LANES = 128
#: a weight block (double buffered beside it) and the weight gradient's
#: float32 accumulator; what a call may ask of VMEM in all
_BLOCK_BYTES = 8 << 20
_ACC_BYTES = 16 << 20
_VMEM_BYTES = 100 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T: the contraction on both last axes
_TN = (((0,), (0,)), ((), ()))  # a.T @ b: on both first axes


def _precision(a):
    # narrow operands multiply exactly on the MXU in one pass, and Mosaic
    # takes no other precision for them; float32 follows the ambient one
    return lax.Precision.DEFAULT if a.dtype.itemsize < 4 else None


# --- the plan: which tile and which group a grid step works on ------------

def _plan(group_sizes, m: int, tile: int, *, every_group: bool):
    """``(n, group, tile, lo, hi)``: the first ``n`` of ``cdiv(m, tile) +
    G - 1`` grid steps each work on row tile ``tile[s]`` for group
    ``group[s]``, whose rows are ``[lo[s], hi[s])``; the steps behind
    repeat the last one and run nothing.  Steps go group by group, a
    group's tiles in order, so a tile two groups share is two
    consecutive steps.

    ``every_group`` (the weight gradient's walk): an empty group has one
    step, ``lo == hi``.  Without it (the row products' walk) empty groups
    have none, and the tiles past the last group follow as steps of a
    group ``G`` with ``lo == hi``: every row tile is visited.

    Sums and compares over (steps, groups) tables, nothing gathered: a
    step's lowering holds this plan once a product."""
    g = group_sizes.shape[0]
    tiles_m = -(-m // tile)
    steps = tiles_m + g - 1
    ends = lax.cumsum(group_sizes)
    starts = ends - group_sizes
    first = lax.div(starts, jnp.int32(tile))
    count = (lax.div(ends + (tile - 1), jnp.int32(tile)) - first) * (group_sizes > 0)
    if every_group:
        count = jnp.maximum(count, 1)
        first = jnp.minimum(first, tiles_m - 1)
    else:
        covered = lax.div(ends[-1:] + (tile - 1), jnp.int32(tile))
        count = lax.concatenate([count, tiles_m - covered], 0)
        first = lax.concatenate([first, covered], 0)
        starts, ends = (lax.concatenate([a, ends[-1:]], 0) for a in (starts, ends))
    stop = lax.cumsum(count)
    begin = stop - count
    n = stop[-1]
    step = jnp.minimum(lax.iota(jnp.int32, steps), n - 1)[:, None]
    mine = ((step >= begin) & (step < stop)).astype(jnp.int32)  # (steps, groups): one 1 a step
    pick = lambda per_group: jnp.sum(mine * per_group, axis=1)  # noqa: E731
    return (n.reshape(1), pick(lax.iota(jnp.int32, count.shape[0])),
            pick(first + step - begin), pick(starts), pick(ends))


def _inside(first_row, count, lo, hi):
    """(count, 1) bool: which of ``count`` rows from ``first_row`` on
    belong to ``[lo, hi)``."""
    row = first_row + lax.broadcasted_iota(jnp.int32, (count, 1), 0)
    return (row >= lo) & (row < hi)


def _masked(x, inside):
    # a select, never a multiply: what lies outside may be anything.  In
    # float32 and back (exact): the v5e's vector unit has no bfloat16
    return jnp.where(inside, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _edge_rows(tile: int) -> int:
    """Rows multiplied at a time where a group ends inside a tile: half
    the tile, or all of it where a half is no whole number of sublane
    groups (16 rows of bfloat16)."""
    return tile if tile % 32 else tile // 2


def _visit(tile_id, tile, lo, hi, multiply, skipped=None):
    """One step's work on its tile for the group ``[lo, hi)``:
    ``multiply(first row, rows)`` over the whole tile, or, where the
    group touches one half of it alone (its first or last rows), over
    that half, and then ``skipped(first row, rows)`` for the other."""
    half = _edge_rows(tile)
    if half == tile:
        return multiply(0, tile)
    middle = tile_id * tile + half
    upper, lower = lo < middle, hi > middle

    @pl.when(upper & lower)
    def _():
        multiply(0, tile)

    @pl.when(~(upper & lower))
    def _():
        multiply(pl.multiple_of(jnp.where(upper, 0, half), half), half)
        if skipped is not None:
            skipped(pl.multiple_of(jnp.where(upper, half, 0), half), half)


# --- rows x weights, and cotangent x weights transposed -------------------

def _rows_kernel(n_ref, src_ref, grp_ref, tile_ref, lo_ref, hi_ref,
                 lhs_ref, w_ref, out_ref, *, tile, transposed):
    del src_ref, grp_ref
    s = pl.program_id(1)
    lo, hi = lo_ref[s], hi_ref[s]
    live = s < n_ref[0]
    # the rows another group wrote on its visit just before stay; on a
    # tile's first visit what the buffer held counts as zero
    again = (s > 0) & (tile_ref[s] == tile_ref[jnp.maximum(s - 1, 0)])

    def multiply(first, count):
        at = pl.ds(first, count)
        a = lhs_ref[at, :]
        y = lax.dot_general(a, w_ref[...], _NT if transposed else (((1,), (0,)), ((), ())),
                            precision=_precision(a), preferred_element_type=jnp.float32)
        rest = jnp.where(again, out_ref[at, :].astype(jnp.float32), 0.0)
        inside = _inside(tile_ref[s] * tile + first, count, lo, hi)
        out_ref[at, :] = jnp.where(inside, y, rest).astype(out_ref.dtype)

    def skipped(first, count):
        @pl.when(~again)
        def _():
            out_ref[pl.ds(first, count), :] = jnp.zeros((count, out_ref.shape[1]), out_ref.dtype)

    @pl.when(live & (hi > lo))
    def _():
        _visit(tile_ref[s], tile, lo, hi, multiply, skipped)

    @pl.when(live & (hi <= lo))
    def _():  # a tile past the groups
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("tile", "block", "transposed", "interpret"))
def _rows_product(lhs, weights, group_sizes, *, tile, block, transposed, interpret):
    """(M, C) x (G, C, O) -> (M, O); ``transposed``: x (G, O, C), the
    contraction over the weights' last axis.  ``block`` columns of O a
    weight block."""
    m, depth = lhs.shape
    width = weights.shape[1 if transposed else 2]
    n, group, tile_id, lo, hi = _plan(group_sizes, m, tile, every_group=False)
    # a step past the groups fetches nothing: it names the row tile and
    # the weight block of the last step that multiplied (the largest of
    # each: the steps go in order)
    real = (hi > lo).astype(jnp.int32)
    src = real * tile_id + (1 - real) * jnp.max(real * tile_id)
    grp = jnp.minimum(real * group + (1 - real) * jnp.max(real * group), weights.shape[0] - 1)
    w_spec = (pl.BlockSpec((None, block, depth), lambda j, s, n, src, grp, *_: (grp[s], j, 0))
              if transposed else
              pl.BlockSpec((None, depth, block), lambda j, s, n, src, grp, *_: (grp[s], 0, j)))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, width), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(width // block, tile_id.shape[0]),
            in_specs=[pl.BlockSpec((tile, depth), lambda j, s, n, src, *_: (src[s], 0)), w_spec],
            out_specs=pl.BlockSpec((tile, block), lambda j, s, n, src, grp, t, *_: (t[s], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="tpuframe_grouped_drows" if transposed else "tpuframe_grouped_fwd",
    )(n, src, grp, tile_id, lo, hi, lhs, weights)


# --- rows transposed x cotangent, group by group --------------------------

def _weights_kernel(n_ref, grp_ref, tile_ref, lo_ref, hi_ref, lhs_ref, g_ref, out_ref,
                    acc_ref, *, tile):
    s = pl.program_id(2)
    n = n_ref[0]
    live = s < n
    lo, hi = lo_ref[s], hi_ref[s]

    @pl.when(live & ((s == 0) | (grp_ref[s] != grp_ref[jnp.maximum(s - 1, 0)])))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def multiply(first, count):
        at = pl.ds(first, count)
        inside = _inside(tile_ref[s] * tile + first, count, lo, hi)
        a, b = _masked(lhs_ref[at, :], inside), _masked(g_ref[at, :], inside)
        acc_ref[...] += lax.dot_general(a, b, _TN, precision=_precision(a),
                                        preferred_element_type=jnp.float32)

    @pl.when(live & (hi > lo))
    def _():
        _visit(tile_ref[s], tile, lo, hi, multiply)

    @pl.when(live & ((s == n - 1) | (grp_ref[s] != grp_ref[jnp.minimum(s + 1, n - 1)])))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "blocks", "interpret"))
def _weights_product(lhs, g, group_sizes, *, tile, blocks, interpret):
    """(M, K) and (M, N) -> (G, K, N): each group's rows transposed times
    its cotangent rows, in ``blocks`` = (rows of K, columns of N) a block."""
    (m, k), width = lhs.shape, g.shape[1]
    bk, bn = blocks
    n, group, tile_id, lo, hi = _plan(group_sizes, m, tile, every_group=True)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, width), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // bk, width // bn, tile_id.shape[0]),
            in_specs=[pl.BlockSpec((tile, bk), lambda i, j, s, n, grp, t, *_: (t[s], i)),
                      pl.BlockSpec((tile, bn), lambda i, j, s, n, grp, t, *_: (t[s], j))],
            out_specs=pl.BlockSpec((None, bk, bn), lambda i, j, s, n, grp, *_: (grp[s], i, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="tpuframe_grouped_dweights",
    )(n, group, tile_id, lo, hi, lhs, g)


# --- block sizes from the shapes -------------------------------------------

def _whole_lanes(width: int, fits) -> int | None:
    """The widest block of a ``width``-wide axis that ``fits``: the axis
    itself, else the most whole lanes that divide it."""
    if fits(width):
        return width
    for lanes in range(width // _LANES - 1, 0, -1):
        if width % (lanes * _LANES) == 0 and fits(lanes * _LANES):
            return lanes * _LANES
    return None


class _Blocks(NamedTuple):
    tile: int        # rows of a grid step's tile
    fwd: int         # columns of N a weight block of the forward product
    drows: int       # rows of K a weight block of the row gradient's
    dweights: tuple  # the weight gradient's (rows of K, columns of N) block


def _blocks(m: int, k: int, n: int, dtype, tile: int | None) -> _Blocks | None:
    """The blocks for (M, K) x (G, K, N) operands of ``dtype``, or None
    where no legal block of the weights fits VMEM.  One rule on the
    shapes: the contraction is never split; the weight's other axis is
    whole where a block is within ``_BLOCK_BYTES``; the weight gradient's
    (K, N) block where its float32 accumulator is within ``_ACC_BYTES``."""
    size = jnp.dtype(dtype).itemsize
    tile = min(tile or _TILE_ROWS, m)
    fwd = _whole_lanes(n, lambda b: k * b * size <= _BLOCK_BYTES)
    drows = _whole_lanes(k, lambda b: n * b * size <= _BLOCK_BYTES)
    wide = _whole_lanes(n, lambda b: _LANES * b * 4 <= _ACC_BYTES)
    tall = wide and _whole_lanes(k, lambda b: b * wide * 4 <= _ACC_BYTES)
    if not (fwd and drows and tall):
        return None
    return _Blocks(tile, fwd, drows, (tall, wide))


def row_tile(m: int, k: int, n: int, dtype) -> int:
    """Rows that the product :func:`grouped_matmul` runs for (M, K) x
    (G, K, N) operands multiplies at a time at a group's edge: half a
    tile of this module's kernels where they engage, a tile of XLA's
    ragged-dot kernel elsewhere.  No event is left."""
    blocks = _blocks(m, k, n, dtype, None)
    if not blocks or _engage(None) is None:
        return RAGGED_TILE_ROWS
    return _edge_rows(blocks.tile)


def _engage(interpret, **verdict):
    # written for one device's whole operands: inside a manual region the
    # reference lowering runs, as wherever GSPMD may have split the weights
    if interpret is None and inside_shard_map():
        return None
    return resolve_interpret(interpret, False, **verdict)


# --- the kernels as one differentiable product ------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(rows, weights, group_sizes, blocks, interpret):
    return _rows_product(rows, weights, group_sizes, tile=blocks.tile, block=blocks.fwd,
                         transposed=False, interpret=interpret)


def _grouped_fwd(rows, weights, group_sizes, blocks, interpret):
    return (_grouped(rows, weights, group_sizes, blocks, interpret),
            (rows, weights, group_sizes))


def _grouped_bwd(blocks, interpret, res, g):
    rows, weights, group_sizes = res
    g = g.astype(rows.dtype)
    d_rows = _rows_product(g, weights, group_sizes, tile=blocks.tile, block=blocks.drows,
                           transposed=True, interpret=interpret)
    d_weights = _weights_product(rows, g, group_sizes, tile=blocks.tile,
                                 blocks=blocks.dweights, interpret=interpret)
    return d_rows, d_weights.astype(weights.dtype), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


# --- the reference lowering ---------------------------------------------------

def _rows_in_groups(x: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``x`` with the rows past ``sum(group_sizes)`` set to zero."""
    inside = jnp.arange(x.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(inside[:, None], x, jnp.zeros((), x.dtype))


@jax.custom_vjp
def _ragged(rows, weights, group_sizes):
    # XLA's TPU kernel writes only the tiles it visits: what lies past the
    # groups is whatever the buffer held (non-finite values, on the chip),
    # and ``0 * garbage`` downstream is not 0
    return _rows_in_groups(jax.lax.ragged_dot(rows, weights, group_sizes), group_sizes)


def _ragged_fwd(rows, weights, group_sizes):
    return _ragged(rows, weights, group_sizes), (rows, weights, group_sizes)


def _ragged_bwd(res, g):
    rows, weights, group_sizes = res
    # ragged_dot's own two transposed products, then the same care: rows
    # past the groups and the weights of empty groups get exact zeros
    _, vjp = jax.vjp(lambda r, w: jax.lax.ragged_dot(r, w, group_sizes), rows, weights)
    d_rows, d_weights = vjp(_rows_in_groups(g, group_sizes))
    d_weights = jnp.where((group_sizes > 0)[:, None, None], d_weights,
                          jnp.zeros((), d_weights.dtype))
    return _rows_in_groups(d_rows, group_sizes), d_weights, None


_ragged.defvjp(_ragged_fwd, _ragged_bwd)


def _choose(rows, weights, group_sizes, interpret, tile_rows, kernels):
    """The operands as the products take them, and the form that runs:
    ``(rows, weights, group_sizes, blocks, interpret)``, ``blocks`` None
    where it is ``jax.lax.ragged_dot``."""
    if rows.ndim != 2 or weights.ndim != 3 or rows.shape[1] != weights.shape[1]:
        raise ValueError(
            f"grouped_matmul takes (M, K) rows and (G, K, N) weights, got "
            f"{rows.shape} and {weights.shape}"
        )
    if group_sizes.shape != (weights.shape[0],):
        raise ValueError(
            f"group_sizes must be ({weights.shape[0]},), got {group_sizes.shape}"
        )
    group_sizes = group_sizes.astype(jnp.int32)
    (m, k), (g, _, n) = rows.shape, weights.shape
    dtype = jnp.result_type(rows.dtype, weights.dtype)
    blocks = _blocks(m, k, n, dtype, tile_rows) if kernels else None
    if blocks is None and kernels and interpret is not None:
        raise ValueError(f"no block of {weights.shape} {dtype} weights fits the kernels' VMEM")
    if blocks is not None:
        interpret = _engage(
            interpret, op="grouped_matmul", shape_class=shape_class(m=m, k=k, n=n, g=g),
            engaged_attrs={"tile_rows": blocks.tile, "edge_rows": _edge_rows(blocks.tile),
                           "forward_block": blocks.fwd, "row_gradient_block": blocks.drows,
                           "weight_gradient_blocks": blocks.dweights})
    if blocks is None or interpret is None:
        return rows, weights, group_sizes, None, None
    return rows.astype(dtype), weights.astype(dtype), group_sizes, blocks, interpret


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array, *,
                   interpret: bool | None = None, tile_rows: int | None = None,
                   kernels: bool = True) -> jax.Array:
    """(M, K) rows x (G, K, N) weights -> (M, N), group by group.

    ``group_sizes`` (G,) int32 with ``sum <= M``; differentiable in
    ``rows`` and ``weights``.  Rows past the groups come out zero, in the
    result and in the gradient, and an empty group's weight gets a zero
    gradient, on every backend.

    ``interpret``: None = auto (this module's kernels on a one-device TPU
    process, ``jax.lax.ragged_dot`` elsewhere).  ``tile_rows`` sets the
    kernels' row tile (a multiple of 16; tests and pricing: the default
    is :func:`row_tile`'s).  ``kernels=False`` keeps ``ragged_dot``
    wherever the call runs (the expert layer's further windows: traffic
    no cell sends, and every kernel call in their loop bodies is a Mosaic
    kernel more for the step's executable to lower, compile and load)."""
    *operands, blocks, interpret = _choose(rows, weights, group_sizes, interpret, tile_rows, kernels)
    return _ragged(*operands) if blocks is None else _grouped(*operands, blocks, interpret)


def grouped_matmul_grads(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array,
                         g: jax.Array, **how) -> tuple[jax.Array, jax.Array]:
    """``(d rows, d weights)`` of :func:`grouped_matmul` under the
    cotangent ``g`` (M, N), by the same dispatch (``how``: its keywords):
    what its gradient computes, for a caller that kept the forward pass's
    arrays and writes its transposes itself.  Going through ``jax.vjp``
    would trace, and in a loop's body lower, a forward product nobody
    reads."""
    *operands, blocks, interpret = _choose(rows, weights, group_sizes, **{
        "interpret": None, "tile_rows": None, "kernels": True, **how})
    d_rows, d_weights, _ = (_ragged_bwd(operands, g) if blocks is None
                            else _grouped_bwd(blocks, interpret, operands, g))
    return d_rows, d_weights


def grouped_matmul_reference(rows: jax.Array, weights: jax.Array,
                             group_sizes: jax.Array) -> jax.Array:
    """jnp oracle: each group's dense product, masked to the group's rows."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    pos = jnp.arange(rows.shape[0])
    out = jnp.zeros((rows.shape[0], weights.shape[2]),
                    jnp.result_type(rows.dtype, weights.dtype))
    for g in range(weights.shape[0]):
        member = (pos >= starts[g]) & (pos < ends[g])
        out = out + jnp.where(member[:, None], rows @ weights[g], 0)
    return out


def tiles_visited(group_sizes: jax.Array, tile_rows: int) -> jax.Array:
    """Row tiles a tiled grouped product multiplies: for each non-empty
    group the tiles its rows touch (a tile two groups share is visited
    by both)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    touched = -(-ends // tile_rows) - starts // tile_rows
    return jnp.sum(jnp.where(group_sizes > 0, touched, 0))
