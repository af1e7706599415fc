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

A second entry, :func:`conv_silu`, is what Qwen3-Next's gated delta rule
reads: the same kind of taps over the ``[q | k | v]`` columns of a fused
projection's output, SiLU, and unit queries and keys a 128-lane head, as
three arrays of the model's rows.  Other mathematics, so kernels of its
own (``tpuframe_conv_silu_fwd`` / ``_bwd``); the tiles, the neighbours'
views and the shifts are this module's.
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

__all__ = ["causal_taps", "conv_silu", "conv_silu_reference", "short_conv",
           "short_conv_reference"]

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


def _whole_groups(x):
    """``x`` (B, L, width) with zeros behind up to whole 16-row groups."""
    pad = pad_to(x.shape[1], _HALO) - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _padded(bch, w, interpret):
    """The kernels on whole 16-row groups: a sequence of another length
    (none a model runs) is padded with zeros behind, which adds nothing."""
    return _fused(_whole_groups(bch), w, interpret)[:, :bch.shape[1]]


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


# -- the second entry: what the gated delta rule reads --------------------------
#: under the root of a unit norm, beside the sum of squares
_UNIT_EPS = 1e-6
#: the body walks a tile's rows in blocks of this many, a chunk of this many
#: columns at a time, as two loops: the body's size, and with it Mosaic's
#: time (11 s for the pair with a tile's arrays whole), does not go with the
#: tile.  Measured on the chip at 8192 x 8192 (forward + backward a call,
#: PR 45): 32 x 256 3.21 ms, 16 x 512 3.16, 64 x 256 2.51, 32 x 512 2.48,
#: whole arrays of 256 x 512 2.46, 64 x 512 2.21
_SILU_ROWS = 64
_SILU_CHUNK = 512
#: what a grid step's blocks may take of `_VMEM_BYTES`, double buffered: the
#: backward's are three rows of all the channels a row of the tile (the
#: input, the three cotangents, the input's cotangent)
_SILU_BLOCK_BYTES = 48 * 2**20


def causal_taps(u: jax.Array, w: jax.Array) -> jax.Array:
    """A depthwise causal convolution along the sequence: ``c_t = sum_j w_j
    u_{t-(K-1)+j}`` of ``u`` (B, L, D) under the taps ``w`` (K, D), zeros
    before the row, as the sum of ``K`` shifted products in float32.  XLA
    makes one fusion of it and the activation that follows."""
    taps, length = w.shape[0], u.shape[1]
    # padded as stored: the one array the fusion reads, not a float32 copy
    u = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    return sum(w32[j] * u[:, j:j + length].astype(jnp.float32) for j in range(taps))


def conv_silu_reference(x: jax.Array, w: jax.Array, *, key_heads: int, key_dim: int):
    """jnp oracle of :func:`conv_silu`: float32 inside, the taps' sum rounded
    to ``x``'s dtype before the SiLU as the source's convolution leaves it,
    and one rounding at the end."""
    dtype, keys = x.dtype, key_heads * key_dim
    act = jax.nn.silu(causal_taps(x[..., :w.shape[1]], w).astype(dtype).astype(jnp.float32))

    def unit(a):
        heads = a.reshape(a.shape[:-1] + (key_heads, key_dim))
        return heads * jax.lax.rsqrt(jnp.sum(heads * heads, axis=-1, keepdims=True) + _UNIT_EPS)

    q, k, v = act[..., :keys], act[..., keys:2 * keys], act[..., 2 * keys:]
    return ((unit(q) * key_dim ** -0.5).astype(dtype).reshape(q.shape),
            unit(k).astype(dtype).reshape(k.shape), v.astype(dtype))


def _parts(keys: int, channels: int, key_dim: int):
    """(part, first column, width, what a unit vector is scaled by: None = no
    norm) of ``[q | k | v]``."""
    return ((0, 0, keys, key_dim ** -0.5), (1, keys, keys, 1.0),
            (2, 2 * keys, channels - 2 * keys, None))


def _tap_sum(taps, shifted):
    """``sum_j w_j shifted[K - 1 - j]`` in the oracle's order; ``shifted[s]``
    is the operand moved ``s`` rows."""
    k = len(taps)
    total = taps[0] * shifted[k - 1]
    for j in range(1, k):
        total = total + taps[j] * shifted[k - 1 - j]
    return total


def _taps(w_ref, cols):
    return [w_ref[pl.ds(j, 1), cols].astype(jnp.float32) for j in range(w_ref.shape[0])]


def _silu_chunk(width: int) -> int:
    return _SILU_CHUNK if width % _SILU_CHUNK == 0 else _LANES


def _chunk_columns(c, base: int, n: int):
    """Chunk ``c`` of ``n`` columns of a part that starts at column ``base`` of
    the fused array: its columns there, and in the part's own array."""
    return (pl.ds(pl.multiple_of(base + c * n, _LANES), n),
            pl.ds(pl.multiple_of(c * n, _LANES), n))


def _silu_tile(length: int, channels: int, itemsize: int) -> int:
    """Rows of a tile: `_TILE_ROWS` (512 bought nothing on the chip), halved
    while the backward's blocks do not fit (float32 rows of more than 8192
    channels)."""
    tile = _TILE_ROWS
    while tile > _HALO and 6 * tile * channels * itemsize > _SILU_BLOCK_BYTES:
        tile //= 2
    return min(tile, length)


def _row_blocks(tile: int):
    """(blocks, rows a block): the kernels walk a tile in blocks of rows
    small enough that a chunk's float32 arrays stay in registers."""
    rows = _SILU_ROWS if tile % _SILU_ROWS == 0 else _HALO
    return tile // rows, rows


def _lane_blocks(fn, *arrays):
    """``fn`` on each 128-lane block (a key head's columns) of the arrays."""
    return jnp.concatenate(
        [fn(*(a[:, c:c + _LANES] for a in arrays))
         for c in range(0, arrays[0].shape[1], _LANES)], axis=1)


def _silu_unit(conv, dtype, scale):
    """SiLU of the taps' sum as stored; with a ``scale``, each block of
    lanes a unit vector times it."""
    c = conv.astype(dtype).astype(jnp.float32)
    act = c * jax.nn.sigmoid(c)
    if scale is None:
        return act

    def unit(a):
        y = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _UNIT_EPS)
        return y if scale == 1.0 else y * scale

    return _lane_blocks(unit, act)


def _silu_unit_transpose(conv, g, dtype, scale):
    """The cotangent of the taps' sum from ``g``, that of `_silu_unit`'s
    result; float32 through the rounding."""
    c = conv.astype(dtype).astype(jnp.float32)
    sig = jax.nn.sigmoid(c)
    slope = sig * (1.0 + c * (1.0 - sig))
    if scale is None:
        return g * slope

    def unit(a, d):
        r = jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _UNIT_EPS)
        y = a * r
        return r * (d - y * jnp.sum(d * y, axis=-1, keepdims=True))

    return _lane_blocks(unit, c * sig, g if scale == 1.0 else g * scale) * slope


def _fwd_part(x_ref, before_ref, w_ref, out_ref, base, width, scale):
    """One of q, k, v: chunks of its columns, and in a chunk the tile's rows
    block by block from the first, a block handing the next its last rows."""
    first = pl.program_id(1) == 0
    dtype = x_ref.dtype
    blocks, rows = _row_blocks(x_ref.shape[1])
    n = _silu_chunk(width)

    def chunk(c, _):
        cols, own = _chunk_columns(c, base, n)
        taps = _taps(w_ref, cols)

        def block(r, u_before):
            at = pl.ds(pl.multiple_of(r * rows, rows), rows)
            u = x_ref[0, at, cols].astype(jnp.float32)
            conv = _tap_sum(taps, [_shifted(u, u_before, s, after=False)
                                   for s in range(len(taps))])
            out_ref[0, at, own] = _silu_unit(conv, dtype, scale).astype(dtype)
            return u[rows - _HALO:]

        jax.lax.fori_loop(0, blocks, block,
                          jnp.where(first, 0.0, before_ref[0, :, cols].astype(jnp.float32)))
        return 0

    jax.lax.fori_loop(0, width // n, chunk, 0)


def _conv_silu_fwd_kernel(x_ref, before_ref, w_ref, *out_refs, keys, channels, key_dim):
    for part, base, width, scale in _parts(keys, channels, key_dim):
        _fwd_part(x_ref, before_ref, w_ref, out_refs[part], base, width, scale)


def _bwd_part(x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref, dx_ref, dw_ref,
              base, width, scale, length):
    """One of q, k, v: chunks of its columns, and in a chunk the tile's rows
    block by block from the last, a block handing the one before it the
    cotangent of the taps' sum on its first rows."""
    i = pl.program_id(1)
    first = i == 0
    dtype = x_ref.dtype
    tile = x_ref.shape[1]
    blocks, rows = _row_blocks(tile)
    n = _silu_chunk(width)
    # rows past the sequence's end (the last tile's, and what follows it)
    # hold whatever the buffer held: they count as zero
    ragged = length % tile != 0

    def load(ref, at, cols, start):
        value = ref[0, at, cols].astype(jnp.float32)
        if not ragged and ref.shape[1] == tile:
            return value    # a row of the tile, and every tile is whole
        return jnp.where(_rows_below(length, start, value.shape), value, 0.0)

    def chunk(c, _):
        cols, own = _chunk_columns(c, base, n)
        taps = _taps(w_ref, cols)
        k = len(taps)
        # the cotangent of the taps' sum on the 16 rows after the tile,
        # which have the tile's last rows before them
        after = (i + 1) * tile
        u_after = load(after_ref, slice(None), cols, after)
        u_last = load(x_ref, pl.ds(tile - _HALO, _HALO), cols, after - _HALO)
        dc_after = _silu_unit_transpose(
            _tap_sum(taps, [_shifted(u_after, u_last, s, after=False) for s in range(k)]),
            load(g_after_ref, slice(None), own, after), dtype, scale)
        u_first = jnp.where(first, 0.0, before_ref[0, :, cols].astype(jnp.float32))

        def block(t, dc_next):
            r = blocks - 1 - t
            r0 = pl.multiple_of(r * rows, rows)
            at = pl.ds(r0, rows)
            u = load(x_ref, at, cols, i * tile + r0)
            before = pl.ds(pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO), _HALO)
            u_before = jnp.where(
                r == 0, u_first, load(x_ref, before, cols, i * tile + r0 - _HALO))
            moved = [_shifted(u, u_before, s, after=False) for s in range(k)]
            dc = _silu_unit_transpose(_tap_sum(taps, moved),
                                      load(g_ref, at, own, i * tile + r0), dtype, scale)
            du = _tap_sum(taps, [_shifted(dc, dc_next, s, after=True) for s in range(k)])
            dx_ref[0, at, cols] = du.astype(dx_ref.dtype)
            for j in range(k):
                # a tap's gradient: eight partial sums a column, added up outside
                dw_ref[j, :, cols] += jnp.sum(
                    (dc * moved[k - 1 - j]).reshape(rows // 8, 8, n), axis=0)
            return dc[:_HALO]

        jax.lax.fori_loop(0, blocks, block, dc_after)
        return 0

    jax.lax.fori_loop(0, width // n, chunk, 0)


def _conv_silu_bwd_kernel(x_ref, before_ref, after_ref, *refs, keys, channels, key_dim, length):
    g_refs, g_after_refs, (w_ref, dx_ref, dw_ref) = refs[0:6:2], refs[1:6:2], refs[6:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for part, base, width, scale in _parts(keys, channels, key_dim):
        _bwd_part(x_ref, before_ref, after_ref, g_refs[part], g_after_refs[part], w_ref,
                  dx_ref, dw_ref, base, width, scale, length)


def _out_shapes(x, keys, channels):
    return tuple(jax.ShapeDtypeStruct(x.shape[:2] + (width,), x.dtype)
                 for width in (keys, keys, channels - 2 * keys))


# Jitted for themselves: one trace and one lowering for a model's layers.
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _conv_silu_fwd_pallas(x, w, key_heads, key_dim, interpret):
    batch, length, _ = x.shape
    k, channels = w.shape
    keys = key_heads * key_dim
    tile = _silu_tile(length, channels, x.dtype.itemsize)
    main, before, _ = _specs(tile, length // _HALO)
    outs = _out_shapes(x, keys, channels)
    # the block of the first `channels` columns: whatever lies behind them
    # in the fused array is never read
    return pl.pallas_call(
        functools.partial(_conv_silu_fwd_kernel, keys=keys, channels=channels,
                          key_dim=key_dim),
        out_shape=outs,
        grid=(batch, pl.cdiv(length, tile)),
        in_specs=[main(channels), before(channels),
                  pl.BlockSpec((k, channels), lambda b, i: (0, 0))],
        out_specs=tuple(main(o.shape[2]) for o in outs),
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_conv_silu_fwd",
    )(x, x, w)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _conv_silu_bwd_pallas(x, w, gs, key_heads, key_dim, interpret):
    batch, length, _ = x.shape
    k, channels = w.shape
    tile = _silu_tile(length, channels, x.dtype.itemsize)
    main, before, after = _specs(tile, length // _HALO)
    dx, dw = pl.pallas_call(
        functools.partial(_conv_silu_bwd_kernel, keys=key_heads * key_dim, channels=channels,
                          key_dim=key_dim, length=length),
        out_shape=(jax.ShapeDtypeStruct((batch, length, channels), x.dtype),
                   jax.ShapeDtypeStruct((k, 8, channels), jnp.float32)),
        grid=(batch, pl.cdiv(length, tile)),
        in_specs=[main(channels), before(channels), after(channels),
                  *(spec(g.shape[2]) for g in gs for spec in (main, after)),
                  pl.BlockSpec((k, channels), lambda b, i: (0, 0))],
        out_specs=(main(channels), pl.BlockSpec((k, 8, channels), lambda b, i: (0, 0, 0))),
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_conv_silu_bwd",
    )(x, x, x, *(g for g in gs for _ in range(2)), w)
    return dx, jnp.sum(dw, axis=1).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_silu(x, w, key_heads, key_dim, interpret):
    return _conv_silu_fwd_pallas(x, w, key_heads, key_dim, interpret)


def _conv_silu_fwd(x, w, key_heads, key_dim, interpret):
    return _conv_silu_fwd_pallas(x, w, key_heads, key_dim, interpret), (x, w)


def _conv_silu_bwd(key_heads, key_dim, interpret, residuals, gs):
    x, w = residuals
    dx, dw = _conv_silu_bwd_pallas(x, w, gs, key_heads, key_dim, interpret)
    # the columns behind [q | k | v] were not read
    return jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[2] - dx.shape[2]))), dw


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _conv_silu_padded(x, w, key_heads, key_dim, interpret):
    """As `_padded`: whole 16-row groups, zeros behind."""
    outs = _conv_silu(_whole_groups(x), w, key_heads, key_dim, interpret)
    return tuple(o[:, :x.shape[1]] for o in outs)


def conv_silu(x: jax.Array, w: jax.Array, *, key_heads: int, key_dim: int,
              interpret: bool | None = None, mesh=None, batch_axes: tuple | None = None):
    """What Qwen3-Next's gated delta rule reads, from the fused projection's
    output ``x`` (B, L, W): its first ``w.shape[1]`` columns are ``[q | k |
    v]`` (``key_heads * key_dim`` each for q and k, the rest v; what lies
    behind them is left alone), which go through the causal depthwise taps
    ``w`` (K, channels), rounded as stored, and SiLU; each head of q and k
    is then a unit vector, q's times ``key_dim ** -0.5``.  -> q, k (B, L,
    key_heads * key_dim), v (B, L, rest) in ``x``'s dtype, the heads side by
    side.  Differentiable in ``x`` and ``w``.

    ``interpret``: None = auto (the kernels on a TPU, by `resolve_interpret`,
    else the jnp oracle, computed again in the backward pass); the op's own
    shape rule asks for whole lanes of every part, a head one block of 128
    lanes, and at most 17 taps.  On a ``mesh`` the kernels run per shard as
    `short_conv`'s do.
    """
    k, channels = w.shape
    keys = key_heads * key_dim
    if x.ndim != 3 or x.shape[-1] < channels or channels <= 2 * keys:
        raise ValueError(
            f"x {x.shape} does not hold [q | k | v] of taps {w.shape} with keys of {keys}")
    oracle = jax.checkpoint(
        functools.partial(conv_silu_reference, key_heads=key_heads, key_dim=key_dim))
    if channels % _LANES or key_dim != _LANES or k - 1 > _HALO:
        if interpret is not None:
            raise ValueError(f"the kernels do not take taps {w.shape} with heads of {key_dim}")
        return oracle(x, w)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, x.shape[0])
    interpret = resolve_interpret(
        interpret, shardable, op="conv_silu",
        shape_class=shape_class(l=x.shape[1], d=channels))
    if interpret is None:
        return oracle(x, w)
    run = functools.partial(_conv_silu_padded, key_heads=key_heads, key_dim=key_dim,
                            interpret=interpret)
    if shardable and n_shards > 1:
        spec = P(axes, None, None)
        return shard_map(run, mesh=mesh, in_specs=(spec, P(None)), out_specs=(spec,) * 3,
                         check_vma=False)(x, w)
    return run(x, w)
