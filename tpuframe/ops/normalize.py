"""Fused image normalization: uint8 → scaled, mean/std-normalized float.

One VMEM pass replaces the reference's three-op torchvision chain
(``ToTensor`` divide-by-255 + ``Normalize`` subtract/divide,
`/root/reference/utils/hf_dataset_utilities.py:70-80`): the uint8 bytes
are read from HBM once and the normalized activation dtype is written
once — the op is HBM-bandwidth-bound, so halving traffic halves time.

Channel constants are compile-time: for channel ``c`` the transform is
``x * w[c] + b[c]`` with ``w = scale/std`` and ``b = -mean/std`` folded
on the host.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from tpuframe.ops.dispatch import batch_sharding_info, resolve_interpret
from tpuframe.ops.ledger import norm_tile_rows, shape_class

_LANES = 128
# row-tile height: domain-clamped knob (TPUFRAME_KERNEL_NORM_TILE_ROWS,
# default 256 -> a 256x128 f32 tile = 128 KiB of VMEM) the kernel
# ledger probes per shape class


def normalize_images_reference(
    images: jax.Array,
    mean: Sequence[float],
    std: Sequence[float],
    scale: float = 1.0 / 255.0,
    out_dtype=jnp.float32,
) -> jax.Array:
    """jnp oracle: ``(images * scale - mean) / std`` over the last axis."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    x = images.astype(jnp.float32) * scale
    return ((x - mean) / std).astype(out_dtype)


def _kernel(x_ref, out_ref, *, weights, biases, n_channels, block_elems):
    i = pl.program_id(0)
    x = x_ref[...]
    if not jnp.issubdtype(x.dtype, jnp.floating):
        # Mosaic has no direct sub-32-bit-int -> float cast; stage via i32.
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    # Channel of each element in the flattened image stream: the last axis
    # of the original (..., C) layout cycles every C elements.
    flat_start = i * block_elems
    idx = flat_start + (
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    )
    ch = idx % n_channels
    w = jnp.full_like(x, weights[0])
    b = jnp.full_like(x, biases[0])
    for c in range(1, n_channels):
        w = jnp.where(ch == c, weights[c], w)
        b = jnp.where(ch == c, biases[c], b)
    out_ref[...] = (x * w + b).astype(out_ref.dtype)


def _pallas_normalize(flat, weights, biases, n_channels, out_dtype, interpret):
    n = flat.shape[0]
    if n % _LANES == 0:
        # Lane-aligned (all common vision shapes): no host-side pad copy;
        # Pallas clips the ragged final row-tile itself.
        rows = n // _LANES
    else:
        rows = -(-n // _LANES)
        flat = jnp.pad(flat, (0, rows * _LANES - n))
    padded = rows * _LANES
    tile = min(norm_tile_rows(), rows)
    kernel = functools.partial(
        _kernel,
        weights=weights,
        biases=biases,
        n_channels=n_channels,
        block_elems=tile * _LANES,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), out_dtype),
        grid=(-(-rows // tile),),
        in_specs=[pl.BlockSpec((tile, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, _LANES), lambda i: (i, 0)),
        interpret=interpret,
        name="tpuframe_normalize",
    )(flat.reshape(rows, _LANES))
    return out.reshape(padded)[:n]


def normalize_images(
    images: jax.Array,
    mean: Sequence[float],
    std: Sequence[float],
    scale: float = 1.0 / 255.0,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    *,
    mesh=None,
    batch_axes: tuple = None,
) -> jax.Array:
    """Fused ``(images * scale - mean) / std``; channels on the last axis.

    ``interpret``: None = auto (compiled kernel on TPU, jnp reference
    elsewhere); True = run the kernel in interpreter mode (tests).

    ``mesh`` + ``batch_axes`` run the kernel per batch shard under
    ``shard_map`` for multi-chip use.  Sharding splits the *leading*
    dim (whole images per shard), so each shard's flattened stream
    starts channel-aligned.  Falls back to the jnp reference when the
    batch doesn't divide.
    """
    n_channels = images.shape[-1]
    mean = tuple(float(m) for m in mean)
    std = tuple(float(s) for s in std)
    if len(mean) != n_channels or len(std) != n_channels:
        raise ValueError(
            f"mean/std length {len(mean)}/{len(std)} != channels {n_channels}"
        )
    axes, n_shards, shardable = batch_sharding_info(
        mesh, batch_axes, images.shape[0] if images.ndim >= 2 else 0
    )
    interpret = resolve_interpret(
        interpret, shardable, op="normalize",
        shape_class=shape_class(n=images.size),
    )
    if interpret is None:
        return normalize_images_reference(images, mean, std, scale, out_dtype)
    weights = tuple(scale / s for s in std)
    biases = tuple(-m / s for m, s in zip(mean, std))

    def run(x):
        out = _pallas_normalize(
            x.reshape(-1), weights, biases, n_channels, out_dtype, interpret
        )
        return out.reshape(x.shape)

    if shardable and n_shards > 1:
        spec = P(axes, *([None] * (images.ndim - 1)))
        return shard_map(
            run, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
        )(images)
    return run(images)
