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

The implementation is ``jax.lax.ragged_dot``.  On the TPU XLA lowers it,
forward and both transposed products of the backward, to its own Mosaic
kernel driven by the group sizes: a grid over row tiles of
:data:`TILE_ROWS` in which only the tiles that hold a group's rows are
multiplied (the compiled module carries ``M / TILE_ROWS + G - 1`` tile
slots and a count of the active ones), so the rows that belong to no
group cost no matmul work: on a v5e 6144 real rows in a buffer of 49152
take 0.78 ms, all 49152 take 4.4 ms (PERF.md, PR 27).  The kernel leaves
the tiles it does not visit unwritten, so this wrapper zeroes the rows
past the groups (one select over the result, which the time above does
not include).  :func:`tiles_visited` counts the tile visits from the
group sizes, for the ``moe/rows_computed`` counter.  Elsewhere (CPU)
``ragged_dot`` runs XLA's reference lowering.

:func:`grouped_matmul_reference` is the oracle: every row against every
group's weight, masked by membership.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "grouped_matmul_reference", "tiles_visited",
           "TILE_ROWS"]

#: rows of one tile of XLA's ragged-dot kernel on the TPU, read off the
#: module it compiles for a v5e (49152 rows, 8 groups: 103 tile slots)
TILE_ROWS = 512


def _rows_in_groups(x: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``x`` with the rows past ``sum(group_sizes)`` set to zero."""
    inside = jnp.arange(x.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(inside[:, None], x, jnp.zeros((), x.dtype))


@jax.custom_vjp
def _grouped(rows, weights, group_sizes):
    # the TPU kernel writes only the tiles it visits: what lies past the
    # groups is whatever the buffer held (non-finite values, on the chip),
    # and ``0 * garbage`` downstream is not 0
    return _rows_in_groups(jax.lax.ragged_dot(rows, weights, group_sizes), group_sizes)


def _grouped_fwd(rows, weights, group_sizes):
    return _grouped(rows, weights, group_sizes), (rows, weights, group_sizes)


def _grouped_bwd(res, g):
    rows, weights, group_sizes = res
    # ragged_dot's own two transposed products, then the same care: rows
    # past the groups and the weights of empty groups get exact zeros
    _, vjp = jax.vjp(lambda r, w: jax.lax.ragged_dot(r, w, group_sizes), rows, weights)
    d_rows, d_weights = vjp(_rows_in_groups(g, group_sizes))
    d_weights = jnp.where((group_sizes > 0)[:, None, None], d_weights,
                          jnp.zeros((), d_weights.dtype))
    return _rows_in_groups(d_rows, group_sizes), d_weights, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """(M, K) rows x (G, K, N) weights -> (M, N), group by group.

    ``group_sizes`` (G,) int32 with ``sum <= M``; differentiable in
    ``rows`` and ``weights``.  Rows past the groups come out zero, in the
    result and in the gradient, and an empty group's weight gets a zero
    gradient, on every backend."""
    if rows.ndim != 2 or weights.ndim != 3 or rows.shape[1] != weights.shape[1]:
        raise ValueError(
            f"grouped_matmul takes (M, K) rows and (G, K, N) weights, got "
            f"{rows.shape} and {weights.shape}"
        )
    if group_sizes.shape != (weights.shape[0],):
        raise ValueError(
            f"group_sizes must be ({weights.shape[0]},), got {group_sizes.shape}"
        )
    return _grouped(rows, weights, group_sizes.astype(jnp.int32))


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


def tiles_visited(group_sizes: jax.Array, tile_rows: int = TILE_ROWS) -> jax.Array:
    """Row tiles a tiled grouped product multiplies: for each non-empty
    group the tiles its rows touch (a tile two groups share is visited
    by both)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    touched = -(-ends // tile_rows) - starts // tile_rows
    return jnp.sum(jnp.where(group_sizes > 0, touched, 0))
