"""Double-gated short convolution (LFM2's ``conv`` operator), fused (Pallas).

Between its two projections the operator is elementwise but for a
``K``-tap causal depthwise convolution along the sequence.  From the
input projection's fused output ``[B | C | h]`` (each ``D`` wide) and the
taps ``w`` (K, D)::

    z   = B * h
    c_t = sum_{j=0..K-1} w_j * z_{t-(K-1)+j}      (z_t = 0 for t < 0)
    out = C * c

each row of the batch on its own, no bias, no activation.  As plain XLA
ops this is several passes over (tokens, D) arrays each way; the kernel
pair reads the three once and writes the result once forward, and
backward reads them and ``d out`` and writes the three gradients, ``z``
and ``c`` computed again from the inputs, the taps accumulated in
float32.  :func:`short_conv_reference` is the oracle, and what a CPU,
``init`` and every call the engage rule turns away run.

The kernels tile the sequence.  A tile's first ``K - 1`` outputs need the
``K - 1`` values of ``z`` before it, and its last ``K - 1`` input
gradients the ``dc`` after it: both arrive as a second, 16-row view of
the same arrays (the rows before the tile, the rows after it), zeroed at
the sequence's ends.
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

__all__ = ["short_conv", "short_conv_reference"]

_LANES = 128
#: rows of the second view that carries a tile's neighbours: one bfloat16
#: sublane tile, so the taps may number up to 17
_HALO = 16
#: sequence rows a grid step holds (of 3 D-wide input rows), and the
#: columns its body works on at a time: sized so that the blocks, double
#: buffered, and the body's float32 temporaries fit `_VMEM_BYTES`
_TILE_ROWS = 256
_CHUNK = 512
_VMEM_BYTES = 64 * 2**20


def short_conv_reference(bch: jax.Array, w: jax.Array) -> jax.Array:
    """jnp oracle: ``bch`` (..., L, 3 D) = ``[B | C | h]``, ``w`` (K, D) ->
    (..., L, D), the products and the taps' sum in float32."""
    k, d = w.shape
    b, c, h = (bch[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3))
    length = bch.shape[-2]
    z = b * h
    z = jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(k - 1, 0), (0, 0)])
    taps = w.astype(jnp.float32)
    conv = sum(taps[j] * z[..., j:j + length, :] for j in range(k))
    return (c * conv).astype(bch.dtype)


def _chunks(d: int):
    step = _CHUNK if d % _CHUNK == 0 else _LANES
    return [(c0, step) for c0 in range(0, d, step)]


def _shifted(x, halo, s, *, after: bool):
    """``x`` (rows, cols) moved ``s`` rows along the sequence: row ``t``
    holds ``x[t - s]`` (``after`` False; the first ``s`` rows come from the
    end of ``halo``, the rows before the tile) or ``x[t + s]`` (``after``;
    the last ``s`` from the start of ``halo``, the rows after it)."""
    if s == 0:
        return x
    rows = x.shape[0]
    if after:
        both = jnp.concatenate([x, halo], axis=0)
        return pltpu.roll(both, rows + _HALO - s, 0)[:rows]
    both = jnp.concatenate([halo, x], axis=0)
    return pltpu.roll(both, s, 0)[_HALO:]


def _load(ref, part, d, c0, n):
    """Columns ``[c0, c0 + n)`` of part 0, 1 or 2 (``B``, ``C``, ``h``) of a
    block of the fused array, in float32."""
    return ref[0, :, pl.ds(part * d + c0, n)].astype(jnp.float32)


def _rows_below(limit, start, shape):
    """(rows, cols) bool: whether a row's position in the sequence,
    counted from ``start``, lies under ``limit``."""
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, 0) < limit


def _fwd_kernel(x_ref, before_ref, w_ref, out_ref, *, d, k):
    first = pl.program_id(1) == 0
    for c0, n in _chunks(d):
        load = functools.partial(_load, d=d, c0=c0, n=n)
        z = load(x_ref, 0) * load(x_ref, 2)
        z_before = jnp.where(first, 0.0, load(before_ref, 0) * load(before_ref, 2))
        conv = jnp.zeros_like(z)
        for j in range(k):
            tap = w_ref[pl.ds(j, 1), pl.ds(c0, n)].astype(jnp.float32)
            conv = conv + tap * _shifted(z, z_before, k - 1 - j, after=False)
        out_ref[0, :, pl.ds(c0, n)] = (load(x_ref, 1) * conv).astype(out_ref.dtype)


def _bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                dx_ref, dw_ref, *, d, k, length, tile):
    i = pl.program_id(1)
    first = i == 0

    @pl.when((pl.program_id(0) == 0) & first)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for c0, n in _chunks(d):
        load = functools.partial(_load, d=d, c0=c0, n=n)
        cols = pl.ds(c0, n)
        # rows past the sequence's end (the last tile's, and what follows
        # it) hold whatever the buffer held: they count as zero
        here = _rows_below(length, i * tile, (tile, n))
        after = _rows_below(length, (i + 1) * tile, (_HALO, n))
        b, c, h = (jnp.where(here, load(x_ref, part), 0.0) for part in range(3))
        g = jnp.where(here, g_ref[0, :, cols].astype(jnp.float32), 0.0)
        z = b * h
        z_before = jnp.where(first, 0.0, load(before_ref, 0) * load(before_ref, 2))
        dc = g * c
        dc_after = jnp.where(
            after, g_after_ref[0, :, cols].astype(jnp.float32) * load(after_ref, 1), 0.0)
        conv = jnp.zeros_like(z)
        dz = jnp.zeros_like(z)
        for j in range(k):
            s = k - 1 - j
            tap = w_ref[pl.ds(j, 1), cols].astype(jnp.float32)
            z_s = _shifted(z, z_before, s, after=False)
            conv = conv + tap * z_s
            dz = dz + tap * _shifted(dc, dc_after, s, after=True)
            # a tap's gradient: eight partial sums a column, added up outside
            dw_ref[j, :, cols] += jnp.sum((dc * z_s).reshape(tile // 8, 8, n), axis=0)
        for part, value in enumerate((dz * h, g * conv, dz * b)):
            dx_ref[0, :, pl.ds(part * d + c0, n)] = value.astype(dx_ref.dtype)


def _tile(length: int) -> int:
    return min(_TILE_ROWS, length)


def _specs(tile: int, halos: int):
    """Block specs of a (B, L, width) array's tile and of its 16-row
    neighbours: the rows before tile ``i``, and the rows after it."""
    per = tile // _HALO
    main = lambda width: pl.BlockSpec((1, tile, width), lambda b, i: (b, i, 0))  # noqa: E731
    before = lambda width: pl.BlockSpec(  # noqa: E731
        (1, _HALO, width), lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0))
    after = lambda width: pl.BlockSpec(  # noqa: E731
        (1, _HALO, width), lambda b, i: (b, jnp.minimum((i + 1) * per, halos - 1), 0))
    return main, before, after


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _fwd_pallas(x, w, interpret):
    batch, length, d3 = x.shape
    k, d = w.shape
    tile = _tile(length)
    main, before, _ = _specs(tile, length // _HALO)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, k=k),
        out_shape=jax.ShapeDtypeStruct((batch, length, d), x.dtype),
        grid=(batch, pl.cdiv(length, tile)),
        in_specs=[main(d3), before(d3), pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=main(d),
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_short_conv_fwd",
    )(x, x, w)


def _bwd_pallas(x, w, g, interpret):
    batch, length, d3 = x.shape
    k, d = w.shape
    tile = _tile(length)
    main, before, after = _specs(tile, length // _HALO)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, k=k, length=length, tile=tile),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((k, 8, d), jnp.float32)),
        grid=(batch, pl.cdiv(length, tile)),
        in_specs=[main(d3), before(d3), after(d3), main(d), after(d),
                  pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=(main(d3), pl.BlockSpec((k, 8, d), lambda b, i: (0, 0, 0))),
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_short_conv_bwd",
    )(x, x, x, g, g, w)
    return dx, jnp.sum(dw, axis=1).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused(x, w, interpret):
    return _fwd_pallas(x, w, interpret)


def _fused_fwd(x, w, interpret):
    return _fwd_pallas(x, w, interpret), (x, w)


def _fused_bwd(interpret, residuals, g):
    return _bwd_pallas(*residuals, g, interpret)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _padded(bch, w, interpret):
    """The kernels on whole 16-row groups: a sequence of another length
    (none a model runs) is padded with zeros behind, which adds nothing."""
    length = bch.shape[1]
    pad = pad_to(length, _HALO) - length
    if pad:
        bch = jnp.pad(bch, ((0, 0), (0, pad), (0, 0)))
    return _fused(bch, w, interpret)[:, :length]


def short_conv(bch: jax.Array, w: jax.Array, interpret: bool | None = None, *,
               mesh=None, batch_axes: tuple | None = None) -> jax.Array:
    """``C * causal_conv(B * h)`` of ``bch`` (B, L, 3 D) = ``[B | C | h]``
    under the taps ``w`` (K, D) -> (B, L, D).  Differentiable in both.

    ``interpret``: None = auto (the kernels on a TPU, the jnp oracle
    elsewhere, by `resolve_interpret`); the op's own shape rule asks for
    whole lanes (``D`` a multiple of 128) and at most 17 taps.  On a
    ``mesh`` whose batch axes divide the rows the kernels run per shard
    under ``shard_map`` (rows are independent; the taps' gradient is
    summed by its transpose).
    """
    k, d = w.shape
    if bch.ndim != 3 or bch.shape[-1] != 3 * d:
        raise ValueError(f"bch {bch.shape} is not (B, L, 3 * {d}) for taps {w.shape}")
    if interpret is None and (d % _LANES or k - 1 > _HALO):
        return short_conv_reference(bch, w)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, bch.shape[0])
    interpret = resolve_interpret(
        interpret, shardable, op="short_conv",
        shape_class=shape_class(l=bch.shape[1], d=d))
    if interpret is None:
        return short_conv_reference(bch, w)
    run = functools.partial(_padded, interpret=interpret)
    if shardable and n_shards > 1:
        spec = P(axes, None, None)
        return shard_map(run, mesh=mesh, in_specs=(spec, P(None)), out_specs=spec,
                         check_vma=False)(bch, w)
    return run(bch, w)
