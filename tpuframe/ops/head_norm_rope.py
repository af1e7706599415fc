"""Head norms and rotary positions of a projection, fused (Pallas).

Between a query or key projection and attention, a Qwen3-shaped model
norms every head and turns it by its position.  For the projection's
own (B, L, H * D) rows, a head ``x`` (D wide) at position ``t``::

    n   = x * rsqrt(mean(x^2) + eps)
    y   = n * scale
    out = y * cos_t + rotate_half(y) * sin_t      (rotate_half: [-y2 | y1])

Tables narrower than the head (a partial rotary factor: ``R`` of ``D``
dimensions) turn the head's first ``R`` dimensions, ``i`` with ``i + R /
2``, and leave the others as ``y``: to the kernels that is a cosine of 1
and a sine of 0 there, and a roll by ``R / 2`` in place of ``D / 2``.

As plain XLA ops on a (B, L, H, D) view this is a handful of float32
passes each way, the statistic over a 128- or 64-wide minor dimension a
fusion of its own and the saved float32 intermediates re-tiled for the
backward pass (PERF.md, PR 34).  The kernel pair reads the rows once and
writes them once forward; backward it reads them and ``d out`` and
writes ``d x``, the row's ``rsqrt`` computed again from the saved input
and ``d scale`` summed in float32 in an output the grid revisits.
Statistics, scale and rotation are float32 with one rounding to the
storage dtype at the end.  :func:`head_norm_rope_reference` is the
oracle, and what a CPU, ``init`` and every call the engage rule turns
away run.

Blocks are whole rows, ``(rows, H * D)``; inside a block the kernels
work on chunks of whole lanes: one head where ``D`` is a multiple of
128, ``128 / D`` heads side by side where it is narrower.  The rotation
is a lane roll by ``D / 2`` within the head with the sign folded into
the sine table, and the tables stay ``(L, chunk)`` in HBM: every head of
a row reads the same block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpuframe.ops.dispatch import batch_sharding_info, pad_to, resolve_interpret
from tpuframe.ops.registry import shape_class

__all__ = ["head_norm_rope", "head_norm_rope_reference"]

_LANES = 128
#: the narrowest head the kernels take: heads side by side in a chunk of
#: 128 lanes cost a masked lane sum each, and 64 is the narrowest measured
_MIN_HEAD = 64
#: sequence rows a grid step holds: a bfloat16 block of 4096 columns is
#: 2 MiB, and the backward's three, double buffered, fit `_VMEM_BYTES`
_TILE_ROWS = 256
#: rows padded to whole bfloat16 sublane tiles
_SUBLANES = 16
_VMEM_BYTES = 64 * 2**20


def head_norm_rope_reference(x: jax.Array, scale: jax.Array, cos: jax.Array,
                             sin: jax.Array, *, num_heads: int, eps: float) -> jax.Array:
    """jnp oracle: ``x`` (B, L, H * D), ``scale`` (D,), ``cos`` / ``sin``
    (L, R), ``R <= D`` -> (B, L, H * D): every head normed, scaled and its
    first ``R`` dimensions turned in float32, rounded once."""
    b, l, width = x.shape
    d, r = width // num_heads, cos.shape[1]
    x32 = x.astype(jnp.float32).reshape(b, l, num_heads, d)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    y = y * scale.astype(jnp.float32)
    if r < d:
        # a branch of its own: the lines below stay what the older models lower to
        rot = jnp.concatenate([-y[..., r // 2:r], y[..., :r // 2]], axis=-1)
        out = jnp.concatenate(
            [y[..., :r] * cos[None, :, None, :] + rot * sin[None, :, None, :], y[..., r:]],
            axis=-1)
        return out.astype(x.dtype).reshape(b, l, width)
    rot = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], axis=-1)
    out = y * cos[None, :, None, :] + rot * sin[None, :, None, :]
    return out.astype(x.dtype).reshape(b, l, width)


def _chunk(d: int) -> int:
    """Lanes the kernels work on at a time: a head, or 128 lanes of
    narrower heads."""
    return max(d, _LANES)


def _lane(v):
    return jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)


def _head_sum(v, d):
    """The sum of ``v`` (rows, chunk) over each head's ``d`` lanes, at
    every lane of the head (or one column, where the chunk is one head)."""
    if v.shape[1] == d:
        return jnp.sum(v, axis=1, keepdims=True)
    head = _lane(v) // d
    out = jnp.zeros_like(v)
    for h in range(v.shape[1] // d):
        mine = head == h
        out = jnp.where(mine, jnp.sum(jnp.where(mine, v, 0.0), axis=1, keepdims=True), out)
    return out


def _half_turn(v, d, r):
    """``v`` (rows, chunk) with the two halves of the first ``r`` lanes of
    every head exchanged: lane ``j < r`` of a head holds the head's lane
    ``(j + r / 2) % r`` (a lane past ``r`` holds one that its sine of 0
    drops)."""
    chunk = v.shape[1]
    if chunk == d == r:
        return pltpu.roll(v, d // 2, 1)
    return jnp.where(_lane(v) % d < r // 2, pltpu.roll(v, chunk - r // 2, 1),
                     pltpu.roll(v, r // 2, 1))


def _rsqrt_mean_square(x, d, eps):
    return jax.lax.rsqrt(_head_sum(x * x, d) * (1.0 / d) + eps)


def _fwd_kernel(x_ref, cos_ref, sin_ref, scale_ref, out_ref, *, d, r, eps):
    chunk = cos_ref.shape[1]
    # y cos + half_turn(y) sin with y = n scale: the scale goes into the tables
    a = cos_ref[...] * scale_ref[pl.ds(0, 1), :]
    b = sin_ref[...] * scale_ref[pl.ds(1, 1), :]
    for c0 in range(0, x_ref.shape[2], chunk):
        cols = pl.ds(c0, chunk)
        x = x_ref[0, :, cols].astype(jnp.float32)
        out_ref[0, :, cols] = (
            _rsqrt_mean_square(x, d, eps) * (x * a + _half_turn(x, d, r) * b)
        ).astype(out_ref.dtype)


def _bwd_kernel(x_ref, g_ref, cos_ref, sin_ref, scale_ref, dx_ref, dscale_ref,
                *, d, r, eps, length):
    i = pl.program_id(0)
    tile, chunk = cos_ref.shape

    @pl.when((i == 0) & (pl.program_id(1) == 0))
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    cos, sin = cos_ref[...], sin_ref[...]
    scale = scale_ref[pl.ds(0, 1), :]
    # rows past the sequence's end (the last tile's) hold whatever the
    # buffer held: they add nothing to the scale's gradient
    here = None
    if length % tile:
        here = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, chunk), 0) < length
    dscale = jnp.zeros((8, chunk), jnp.float32)
    for c0 in range(0, x_ref.shape[2], chunk):
        cols = pl.ds(c0, chunk)
        x = x_ref[0, :, cols].astype(jnp.float32)
        g = g_ref[0, :, cols].astype(jnp.float32)
        rms = _rsqrt_mean_square(x, d, eps)
        n = x * rms
        dy = g * cos + _half_turn(g, d, r) * sin
        part = dy * n if here is None else jnp.where(here, dy * n, 0.0)
        # eight partial sums a column, added up outside
        dscale = dscale + jnp.sum(part.reshape(tile // 8, 8, chunk), axis=0)
        dn = dy * scale
        dx = rms * (dn - n * (_head_sum(dn * n, d) * (1.0 / d)))
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
    dscale_ref[...] += dscale


def _specs(tile: int, width: int, chunk: int):
    rows = pl.BlockSpec((1, tile, width), lambda i, b: (b, i, 0))
    table = pl.BlockSpec((tile, chunk), lambda i, b: (i, 0))
    scale = pl.BlockSpec((2, chunk), lambda i, b: (0, 0))
    return rows, table, scale


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _tables(scale, cos, sin, d, *, transpose=False):
    """What a kernel multiplies by, side by side over a chunk's lanes:
    ``cos``; ``sin`` with rotate-half's sign (its halves exchanged for the
    transpose: ``d y = g cos + half_turn(g sin_signed)``); the scale at a
    lane and at the lane it is exchanged with.  Tables of ``r < d`` columns
    are widened to the head: cosine 1 and sine 0 past ``r``, the exchange
    inside it."""
    r = cos.shape[1]
    sin = sin * jnp.where(jnp.arange(r) < r // 2, -1.0, 1.0)
    if transpose:
        sin = jnp.roll(sin, r // 2, axis=1)
    scale = scale.astype(jnp.float32)
    if r < d:
        cos = jnp.pad(cos, ((0, 0), (0, d - r)), constant_values=1.0)
        sin = jnp.pad(sin, ((0, 0), (0, d - r)))
        scales = jnp.stack([scale, jnp.concatenate([jnp.roll(scale[:r], r // 2), scale[r:]])])
    else:
        scales = jnp.stack([scale, jnp.roll(scale, d // 2)])
    return tuple(jnp.tile(t, (1, _chunk(d) // d)) for t in (cos, sin, scales))


def _fwd_pallas(x, scale, cos, sin, num_heads, eps, interpret):
    batch, length, width = x.shape
    d = width // num_heads
    tile = min(_TILE_ROWS, length)
    rows, table, scales = _specs(tile, width, _chunk(d))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, r=cos.shape[1], eps=eps),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(pl.cdiv(length, tile), batch),
        in_specs=[rows, table, table, scales],
        out_specs=rows,
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_head_norm_rope_fwd",
    )(x, *_tables(scale, cos, sin, d))


def _bwd_pallas(x, scale, cos, sin, g, num_heads, eps, interpret):
    batch, length, width = x.shape
    d = width // num_heads
    chunk = _chunk(d)
    tile = min(_TILE_ROWS, length)
    rows, table, scales = _specs(tile, width, chunk)
    dx, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, r=cos.shape[1], eps=eps, length=length),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((8, chunk), jnp.float32)),
        grid=(pl.cdiv(length, tile), batch),
        in_specs=[rows, rows, table, table, scales],
        out_specs=(rows, pl.BlockSpec((8, chunk), lambda i, b: (0, 0))),
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_head_norm_rope_bwd",
    )(x, g, *_tables(scale, cos, sin, d, transpose=True))
    return dx, jnp.sum(dscale.reshape(-1, d), axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(x, scale, cos, sin, num_heads, eps, interpret):
    return _fwd_pallas(x, scale, cos, sin, num_heads, eps, interpret)


def _fused_fwd(x, scale, cos, sin, num_heads, eps, interpret):
    return _fwd_pallas(x, scale, cos, sin, num_heads, eps, interpret), (x, scale, cos, sin)


def _fused_bwd(num_heads, eps, interpret, residuals, g):
    _, _, cos, sin = residuals
    dx, dscale = _bwd_pallas(*residuals, g, num_heads, eps, interpret)
    # positions are no parameters: the tables get no gradient
    return dx, dscale, jnp.zeros_like(cos), jnp.zeros_like(sin)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _padded(x, scale, cos, sin, *, num_heads, eps, interpret):
    """The kernels on whole 16-row groups: a sequence of another length
    (none a model runs) is padded with zeros behind, which adds nothing."""
    length = x.shape[1]
    pad = pad_to(length, _SUBLANES) - length
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        cos, sin = (jnp.pad(t, ((0, pad), (0, 0))) for t in (cos, sin))
    return _fused(x, scale, cos, sin, num_heads, eps, interpret)[:, :length]


def head_norm_rope(x: jax.Array, scale: jax.Array, cos: jax.Array, sin: jax.Array,
                   *, num_heads: int, eps: float, interpret: bool | None = None,
                   mesh=None, batch_axes: tuple | None = None) -> jax.Array:
    """``rope(rms_norm_per_head(x) * scale)`` of a projection's rows ``x``
    (B, L, H * D) under the head norm's ``scale`` (D,) and the rotary
    tables ``cos`` / ``sin`` (L, R) at the rows' position ids (rotate-half
    convention, `models.transformer.rope_tables`; ``R <= D`` turns a head's
    first ``R`` dimensions) -> (B, L, H * D).
    Differentiable in ``x`` and ``scale``; the tables get no gradient.

    ``interpret``: None = auto (the kernels on a TPU, the jnp oracle
    elsewhere, by `resolve_interpret`); the op's own shape rule asks for
    whole lanes (``H * D`` a multiple of 128) and heads of 64 lanes or
    a multiple of 128.  On a ``mesh`` whose batch axes divide the
    rows the kernels run per shard under ``shard_map`` (rows are
    independent; the scale's gradient is summed by its transpose).
    """
    if x.ndim != 3 or x.shape[-1] % num_heads:
        raise ValueError(f"x {x.shape} is not (B, L, {num_heads} * D)")
    d = x.shape[-1] // num_heads
    r = cos.shape[-1]
    if (scale.shape != (d,) or cos.shape != (x.shape[1], r) or sin.shape != cos.shape
            or r > d or r % 2):
        raise ValueError(f"scale {scale.shape} and tables {cos.shape}, {sin.shape} are not "
                         f"({d},) and ({x.shape[1]}, R) with an even R <= {d} for x {x.shape}")
    oracle = functools.partial(head_norm_rope_reference, num_heads=num_heads, eps=eps)
    if interpret is None and (x.shape[-1] % _LANES or d < _MIN_HEAD
                              or (d % _LANES and _LANES % d)):
        return oracle(x, scale, cos, sin)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, x.shape[0])
    interpret = resolve_interpret(
        interpret, shardable, op="head_norm_rope",
        shape_class=shape_class(l=x.shape[1], h=num_heads, d=d))
    if interpret is None:
        return oracle(x, scale, cos, sin)
    run = functools.partial(_padded, num_heads=num_heads, eps=eps, interpret=interpret)
    if shardable and n_shards > 1:
        spec = P(axes, None, None)
        return shard_map(run, mesh=mesh,
                         in_specs=(spec, P(None), P(None, None), P(None, None)),
                         out_specs=spec, check_vma=False)(x, scale, cos, sin)
    return run(x, scale, cos, sin)
