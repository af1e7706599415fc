"""Fused quantized-wire kernels: amax, scale, round, pack in one VMEM pass.

The compressed gradient wire (``parallel.compression``) spends its
device time in three elementwise stages — per-bucket abs-max, the
scale/round/clip encode, and the dequantize-to-mean decode.  Staged as
separate XLA ops they are recurring top-op offenders in the profiler's
``device_time.top_ops`` table (convert/round/clamp class); each stage
re-streams the full bucket array through HBM.  The kernels here do each
stage in one VMEM pass over (buckets, elems) tiles, with the per-bucket
scale column riding along as a lane-broadcast input.

Triple-path contract (``ops.dispatch``): compiled Pallas on TPU,
interpret mode anywhere under ``TPUFRAME_PALLAS_INTERPRET=1``, and a
jnp reference otherwise.  The references reproduce the compression
module's arithmetic *expression for expression* — the wire's
bit-exactness pins (staged vs fused, grouped vs single-shot) ride on
encode/decode bits never depending on which path ran.

Block sizing: ``TPUFRAME_COMMS_FUSED_BLOCK`` (declared in
``parallel.comms_env``) sets the column-block element count; rows tile
by 8 (the f32 sublane minimum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpuframe.ops.dispatch import pad_to, resolve_interpret
from tpuframe.parallel.comms_env import comms_fused_block

__all__ = [
    "bucket_abs_max",
    "bucket_abs_max_reference",
    "quant_encode",
    "quant_encode_reference",
    "quant_decode",
    "quant_decode_reference",
]

_LANES = 128
_TILE_ROWS = 8
_QMAX = 127.0    # symmetric int8 grid (== compression._QMAX)
_FP8_MAX = 448.0  # e4m3 finite max (== compression._FP8_MAX)


def _tiny():
    return jnp.finfo(jnp.float32).tiny


# -- jnp references (the arithmetic contract) ---------------------------------


def bucket_abs_max_reference(v):
    """Per-bucket abs-max of a (buckets, elems) array, keepdims."""
    return jnp.max(jnp.abs(v), axis=1, keepdims=True)


def quant_encode_reference(v, amax, mode: str, noise=None):
    """Quantize ``v`` against per-bucket ``amax`` (broadcast-ready):
    ``(payload, deq)`` with the exact expressions the staged wire uses —
    int8: symmetric grid, ``floor(x + noise)`` when ``noise`` is given
    (unbiased stochastic rounding) else round-to-nearest; fp8-e4m3:
    amax mapped onto the 448 grid, RTNE via the dtype cast."""
    denom = jnp.maximum(amax, _tiny())
    if mode == "fp8":
        q = ((v / denom) * _FP8_MAX).astype(jnp.float8_e4m3fn)
        return q.astype(jnp.float32), denom / _FP8_MAX
    scale = denom / _QMAX
    x = v / scale
    x = jnp.floor(x + noise) if noise is not None else jnp.round(x)
    q = jnp.clip(x, -_QMAX, _QMAX)
    return q.astype(jnp.int32), scale


def quant_decode_reference(total, amax, mode: str, world: int):
    """Summed payloads back to mean gradient units, with the wire's
    non-finite propagation: a bucket whose agreed amax is inf/nan
    decodes to NaN (divergence must look like divergence)."""
    grid = _FP8_MAX if mode == "fp8" else _QMAX
    deq = jnp.maximum(amax, _tiny()) / grid
    mean = total.astype(jnp.float32) * deq / world
    return jnp.where(jnp.isfinite(amax), mean, jnp.nan)


# -- Pallas kernels -----------------------------------------------------------


def _amax_kernel(v_ref, out_ref):
    import jax.experimental.pallas as pl

    part = jnp.max(jnp.abs(v_ref[...]), axis=1, keepdims=True)
    part = jnp.broadcast_to(part, out_ref.shape)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        out_ref[...] = jnp.maximum(out_ref[...], part)


def _encode_int8_kernel(v_ref, amax_ref, q_ref):
    scale = jnp.maximum(amax_ref[...][:, :1], _tiny()) / _QMAX
    x = jnp.round(v_ref[...] / scale)
    q_ref[...] = jnp.clip(x, -_QMAX, _QMAX).astype(jnp.int32)


def _encode_int8_sr_kernel(v_ref, amax_ref, noise_ref, q_ref):
    scale = jnp.maximum(amax_ref[...][:, :1], _tiny()) / _QMAX
    x = jnp.floor(v_ref[...] / scale + noise_ref[...])
    q_ref[...] = jnp.clip(x, -_QMAX, _QMAX).astype(jnp.int32)


def _encode_fp8_kernel(v_ref, amax_ref, q_ref):
    denom = jnp.maximum(amax_ref[...][:, :1], _tiny())
    q = ((v_ref[...] / denom) * _FP8_MAX).astype(jnp.float8_e4m3fn)
    q_ref[...] = q.astype(jnp.float32)


def _decode_kernel(t_ref, amax_ref, out_ref, *, grid_max, world):
    amax = amax_ref[...][:, :1]
    deq = jnp.maximum(amax, _tiny()) / grid_max
    mean = t_ref[...].astype(jnp.float32) * deq / world
    out_ref[...] = jnp.where(jnp.isfinite(amax), mean, jnp.nan)


def _tiles(nb: int, be: int) -> tuple[int, int, int]:
    """(padded_rows, padded_cols, col_block) for a (nb, be) launch."""
    block = min(comms_fused_block(), pad_to(be, _LANES))
    return pad_to(nb, _TILE_ROWS), pad_to(be, block), block


def _pad2(x, rows: int, cols: int):
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x


def _amax_lanes(amax, rows: int):
    """The per-bucket scale column as a lane-broadcast (rows, _LANES)
    block so it tiles legally next to the payload blocks."""
    full = jnp.broadcast_to(amax, (amax.shape[0], _LANES))
    return jnp.pad(full, ((0, rows - amax.shape[0]), (0, 0)))


def _pallas_bucket_abs_max(v, interpret: bool):
    import jax.experimental.pallas as pl

    nb, be = v.shape
    rows, cols, block = _tiles(nb, be)
    out = pl.pallas_call(
        _amax_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        grid=(rows // _TILE_ROWS, cols // block),
        in_specs=[pl.BlockSpec((_TILE_ROWS, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_TILE_ROWS, _LANES), lambda i, j: (i, 0)),
        interpret=interpret,
        name="tpuframe_quant_amax",
    )(_pad2(v, rows, cols))
    return out[:nb, :1]


def _pallas_encode(v, amax, mode: str, noise, interpret: bool):
    import jax.experimental.pallas as pl

    nb, be = v.shape
    rows, cols, block = _tiles(nb, be)
    vspec = pl.BlockSpec((_TILE_ROWS, block), lambda i, j: (i, j))
    aspec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i, j: (i, 0))
    operands = [_pad2(v, rows, cols), _amax_lanes(amax, rows)]
    in_specs = [vspec, aspec]
    if mode == "fp8":
        kernel, out_dtype = _encode_fp8_kernel, jnp.float32
    elif noise is not None:
        kernel, out_dtype = _encode_int8_sr_kernel, jnp.int32
        operands.append(_pad2(noise, rows, cols))
        in_specs.append(vspec)
    else:
        kernel, out_dtype = _encode_int8_kernel, jnp.int32
    q = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        grid=(rows // _TILE_ROWS, cols // block),
        in_specs=in_specs,
        out_specs=vspec,
        interpret=interpret,
        name="tpuframe_quant_encode",
    )(*operands)
    return q[:nb, :be]


def _pallas_decode(total, amax, mode: str, world: int, interpret: bool):
    import jax.experimental.pallas as pl

    nb, be = total.shape
    rows, cols, block = _tiles(nb, be)
    vspec = pl.BlockSpec((_TILE_ROWS, block), lambda i, j: (i, j))
    aspec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i, j: (i, 0))
    kernel = functools.partial(
        _decode_kernel,
        grid_max=_FP8_MAX if mode == "fp8" else _QMAX,
        world=world,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        grid=(rows // _TILE_ROWS, cols // block),
        in_specs=[vspec, aspec],
        out_specs=vspec,
        interpret=interpret,
        name="tpuframe_quant_decode",
    )(_pad2(total, rows, cols), _amax_lanes(amax, rows))
    return out[:nb, :be]


# -- dispatchers --------------------------------------------------------------


def _bucketed(v, amax=None) -> bool:
    """Kernel-eligible shape: f32-compatible (buckets, elems) payload
    with an optional (buckets, 1) scale column."""
    if v.ndim != 2 or v.size == 0:
        return False
    if amax is not None and tuple(amax.shape) != (v.shape[0], 1):
        return False
    return True


def bucket_abs_max(v, interpret: bool | None = None):
    """Per-bucket abs-max of a (buckets, elems) array, keepdims — the
    scale-agreement input for the compressed wire."""
    interp = resolve_interpret(interpret, shardable=False, op="quant_wire")
    if interp is None or not _bucketed(v):
        return bucket_abs_max_reference(v)
    return _pallas_bucket_abs_max(v.astype(jnp.float32), bool(interp))


def quant_encode(v, amax, mode: str, noise=None,
                 interpret: bool | None = None):
    """Encode a (buckets, elems) payload against agreed per-bucket
    scales: ``(payload, deq)``, scale + round + clip + pack in one VMEM
    pass when the kernel engages.  ``noise`` (same shape as ``v``)
    selects unbiased stochastic rounding on the int8 grid; fp8 ignores
    it (RTNE in the dtype cast)."""
    interp = resolve_interpret(interpret, shardable=False, op="quant_wire")
    if interp is None or not _bucketed(v, amax):
        return quant_encode_reference(v, amax, mode, noise)
    denom = jnp.maximum(amax, _tiny())
    deq = denom / (_FP8_MAX if mode == "fp8" else _QMAX)
    q = _pallas_encode(
        v.astype(jnp.float32), amax, mode,
        None if mode == "fp8" else noise, bool(interp),
    )
    return q, deq


def quant_decode(total, amax, mode: str, world: int,
                 interpret: bool | None = None):
    """Decode summed payloads to the mean gradient (dequant + divide +
    non-finite propagation fused), matching
    :func:`quant_decode_reference` bit-for-bit."""
    interp = resolve_interpret(interpret, shardable=False, op="quant_wire")
    if interp is None or not _bucketed(total, amax):
        return quant_decode_reference(total, amax, mode, world)
    return _pallas_decode(total, amax, mode, int(world), bool(interp))
