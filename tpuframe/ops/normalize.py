"""Image normalization: uint8 → scaled, mean/std-normalized float.

The reference's torchvision chain (``ToTensor`` divide-by-255 +
``Normalize`` subtract/divide,
`/root/reference/utils/hf_dataset_utilities.py:70-80`) as one pass on
the device: for channel ``c`` the transform is ``x * w[c] + b[c]`` in
float32 with ``w = scale/std`` and ``b = -mean/std`` folded on the
host, rounded once to ``out_dtype``.

Plain ``jnp`` on the array as it is shaped, on every backend.  XLA
fuses convert, multiply and add into one pass under a jit, reads the
parameter in the layout it arrived in and writes the layout the first
convolution asks for; GSPMD shards it without a ``shard_map``.  A Pallas
kernel over a flat ``(rows, 128)`` view of the batch used to stand here:
on a v5e it took 0.36 ms for 256 images of 224 px and the re-layouts a
custom call forces around it 26 ms (PERF.md, PR 25).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


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


def normalize_images(
    images: jax.Array,
    mean: Sequence[float],
    std: Sequence[float],
    scale: float = 1.0 / 255.0,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``(images * scale - mean) / std`` with the constants folded;
    channels on the last axis."""
    n_channels = images.shape[-1]
    mean = tuple(float(m) for m in mean)
    std = tuple(float(s) for s in std)
    if len(mean) != n_channels or len(std) != n_channels:
        raise ValueError(
            f"mean/std length {len(mean)}/{len(std)} != channels {n_channels}"
        )
    w = jnp.asarray([scale / s for s in std], jnp.float32)
    b = jnp.asarray([-m / s for m, s in zip(mean, std)], jnp.float32)
    return (images.astype(jnp.float32) * w + b).astype(out_dtype)
