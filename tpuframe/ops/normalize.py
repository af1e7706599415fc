"""Image normalization: uint8 → scaled, mean/std-normalized float.

The reference's torchvision chain (``ToTensor`` divide-by-255 +
``Normalize`` subtract/divide,
`/root/reference/utils/hf_dataset_utilities.py:70-80`) as one pass on
the device: for channel ``c`` the transform is ``x * w[c] + b[c]`` in
float32 with ``w = scale/std`` and ``b = -mean/std`` folded on the
host, rounded once to ``out_dtype``.

Two forms of that pass, chosen by the input's shape:

- an image batch (channels-last, 3 or 1 in the minor dimension) is
  normalized in its own layout by plain ``jnp``.  XLA fuses convert,
  multiply and add into one pass under a jit (it always did: the chain
  was never three passes), reads the parameter in the layout it
  arrived in and writes the layout the first convolution asks for.
- the Pallas kernel works on a flat ``(rows, 128)`` stream, and stays
  for inputs whose flat view is a bitcast (1-D, or a last dimension of
  whole 128-lane rows).  For an image batch that view is a physical
  re-layout on the chip, and a custom call pins the layout on both of
  its sides: on a v5e the kernel took 0.36 ms for 256 images of 224 px
  and the reshapes and copies around it 26 ms (PERF.md, PR 25), so auto
  dispatch no longer sends image batches through it.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from tpuframe.ops import dispatch
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


def _flat_view_is_free(shape: tuple) -> bool:
    """Is ``reshape(-1, 128)`` a bitcast on the chip?  The TPU tiles the
    two minor dimensions, so only a 1-D array or one whose last dimension
    is whole 128-lane rows lies in memory as the flat stream the kernel
    reads; any other shape (every NHWC image batch) is re-laid-out."""
    return len(shape) <= 1 or shape[-1] % _LANES == 0


def _normalize_in_layout(images, weights, biases, out_dtype):
    """The kernel's arithmetic on the array as it is shaped: float32
    ``x * w[c] + b[c]``, one rounding.  Elementwise, so XLA fuses it into
    one pass and GSPMD shards it without a ``shard_map``."""
    w = jnp.asarray(weights, jnp.float32)
    b = jnp.asarray(biases, jnp.float32)
    return (images.astype(jnp.float32) * w + b).astype(out_dtype)


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

    ``interpret``: None = auto (on TPU the compiled kernel, or, for an
    input whose flat view is not free there, which every image batch is,
    :func:`_normalize_in_layout` with one ``ops/kernel_verdict`` event of
    ``source="layout"``; the jnp reference elsewhere); True/False = run
    the kernel, interpreted (tests) or compiled, whatever the shape.

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
    weights = tuple(scale / s for s in std)
    biases = tuple(-m / s for m, s in zip(mean, std))
    shape_cls = shape_class(n=images.size)
    if (interpret is None and dispatch.pallas_mode() is not None
            and not _flat_view_is_free(images.shape)):
        # decided before the ledger and TPUFRAME_KERNELS are asked: they
        # price the kernel standing alone, not the layout changes it forces
        dispatch._emit_verdict(
            "normalize", shape_cls, enable=False, source="layout")
        return _normalize_in_layout(images, weights, biases, out_dtype)
    axes, n_shards, shardable = batch_sharding_info(
        mesh, batch_axes, images.shape[0] if images.ndim >= 2 else 0
    )
    interpret = resolve_interpret(
        interpret, shardable, op="normalize", shape_class=shape_cls,
    )
    if interpret is None:
        return normalize_images_reference(images, mean, std, scale, out_dtype)

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
