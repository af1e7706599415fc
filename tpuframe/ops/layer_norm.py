"""Fused LayerNorm with a recompute backward (Pallas).

LayerNorm is pure HBM bandwidth: the unfused path reads the (N, D)
activations for the moments, again for the normalize, and the backward
re-reads them plus the saved mean/rstd.  The fused forward computes
moments and the affine in one VMEM pass; the backward recomputes the
statistics from the saved inputs in VMEM (nothing but x/scale/bias is
saved) and emits dx in one pass plus per-block partial reductions for
dscale/dbias that sum on-chip afterwards.

Semantics match ``flax.linen.LayerNorm`` defaults (f32 statistics,
fast-variance E[x^2]-E[x]^2, epsilon inside the rsqrt), so the
transformer/ViT blocks can swap implementations without retraining.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import (
    DATA_AXIS,
    FSDP_AXIS,
    SEQUENCE_AXIS,
    current_runtime,
)
from tpuframe.ops.dispatch import batch_sharding_info, pad_to, resolve_interpret

_ROWS = 16
_LANES = 128


def layer_norm_reference(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6
) -> jax.Array:
    """jnp oracle: normalize over the last axis, f32 stats, affine."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, -1, keepdims=True) - mu * mu, 0.0)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, *, d, eps):
    x = x_ref[...].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < d
    xm = jnp.where(valid, x, 0.0)
    mu = jnp.sum(xm, 1, keepdims=True) / d
    var = jnp.maximum(jnp.sum(xm * xm, 1, keepdims=True) / d - mu * mu, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    y_ref[...] = jnp.where(valid, y, 0.0).astype(y_ref.dtype)


def _bwd_kernel(x_ref, scale_ref, g_ref, dx_ref, dscale_ref, dbias_ref, *, d, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < d
    xm = jnp.where(valid, x, 0.0)
    mu = jnp.sum(xm, 1, keepdims=True) / d
    var = jnp.maximum(jnp.sum(xm * xm, 1, keepdims=True) / d - mu * mu, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = jnp.where(valid, (x - mu) * rstd, 0.0)
    gs = jnp.where(valid, g * scale_ref[...].astype(jnp.float32), 0.0)
    # dx = rstd * (gs - mean(gs) - xhat * mean(gs * xhat))
    m1 = jnp.sum(gs, 1, keepdims=True) / d
    m2 = jnp.sum(gs * xhat, 1, keepdims=True) / d
    dx = rstd * (gs - m1 - xhat * m2)
    dx_ref[...] = jnp.where(valid, dx, 0.0).astype(dx_ref.dtype)
    gv = jnp.where(valid, g, 0.0)
    # Affine-grad partials accumulate into ONE (_ROWS, dp) block revisited
    # by every grid step (the sequential-grid accumulation pattern): a
    # per-step (1, dp) output block would violate Mosaic's (8, 128) tile
    # minimum whenever the grid has >1 step.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    dscale_ref[...] += gv * xhat
    dbias_ref[...] += gv


def _pad_rows(x):
    n, d = x.shape
    np_, dp = pad_to(n, _ROWS), pad_to(d, _LANES)
    return jnp.pad(x, ((0, np_ - n), (0, dp - d))), n, d, np_, dp


def _pad_affine(v, dp):
    return jnp.pad(v, (0, dp - v.shape[0]))[None, :]


def _fwd_pallas(x, scale, bias, eps, interpret):
    xp, n, d, np_, dp = _pad_rows(x)
    sp, bp = _pad_affine(scale, dp), _pad_affine(bias, dp)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, eps=eps),
        out_shape=jax.ShapeDtypeStruct((np_, dp), x.dtype),
        grid=(np_ // _ROWS,),
        in_specs=[
            pl.BlockSpec((_ROWS, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, dp), lambda i: (i, 0)),
        interpret=interpret,
        name="tpuframe_layer_norm_fwd",
    )(xp, sp, bp)
    return y[:n, :d]


def _bwd_pallas(x, scale, g, eps, interpret):
    xp, n, d, np_, dp = _pad_rows(x)
    sp = _pad_affine(scale, dp)
    gp = jnp.pad(g, ((0, np_ - n), (0, dp - d)))
    blocks = np_ // _ROWS
    dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((np_, dp), x.dtype),
            jax.ShapeDtypeStruct((_ROWS, dp), jnp.float32),
            jax.ShapeDtypeStruct((_ROWS, dp), jnp.float32),
        ),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((_ROWS, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((_ROWS, dp), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((_ROWS, dp), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, dp), lambda i: (0, 0)),
            pl.BlockSpec((_ROWS, dp), lambda i: (0, 0)),
        ),
        interpret=interpret,
        name="tpuframe_layer_norm_bwd",
    )(xp, sp, gp)
    dscale = jnp.sum(dscale_p, 0)[:d].astype(scale.dtype)
    dbias = jnp.sum(dbias_p, 0)[:d].astype(scale.dtype)
    return dx[:n, :d], dscale, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused(x, scale, bias, eps, interpret):
    return _fwd_pallas(x, scale, bias, eps, interpret)


def _fused_fwd(x, scale, bias, eps, interpret):
    return _fwd_pallas(x, scale, bias, eps, interpret), (x, scale)


def _fused_bwd(eps, interpret, residuals, g):
    x, scale = residuals
    dx, dscale, dbias = _bwd_pallas(x, scale, g, eps, interpret)
    return dx, dscale, dbias


_fused.defvjp(_fused_fwd, _fused_bwd)


def _spec_shard_info(mesh, spec, shape):
    """(total_shards, divisible) for an x PartitionSpec over lead dims."""
    total, ok = 1, True
    for dim, entry in zip(shape[:-1], tuple(spec)[:-1]):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape.get(n, 1) for n in names]))
        total *= size
        if size > 1 and dim % size:
            ok = False
    return total, ok


def fused_layer_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-6,
    interpret: bool | None = None,
    *,
    mesh=None,
    batch_axes: tuple = None,
    spec: P | None = None,
) -> jax.Array:
    """LayerNorm over the last axis of ``(..., D)`` with (D,) affine.

    Differentiable (x, scale, bias) via the recompute backward kernels.
    ``interpret``: None = auto (kernel on TPU, jnp oracle elsewhere).

    Multi-chip: rows are independent, so any sharding of the *leading*
    dims runs the kernel per shard under ``shard_map`` (the
    replicated-affine gradient is psummed by shard_map's transpose).
    Pass either ``batch_axes`` (leading-dim axes only) or a full ``spec``
    PartitionSpec for ``x`` whose last entry is None — e.g.
    ``P(("data", "fsdp"), "seq", None)`` for a sequence-parallel (B, L, D).
    Falls back to the jnp reference when the dims don't divide.
    """
    if scale.shape != x.shape[-1:] or bias.shape != x.shape[-1:]:
        raise ValueError(
            f"scale/bias shapes {scale.shape}/{bias.shape} != (.., {x.shape[-1]})"
        )
    lead = x.shape[:-1]
    from tpuframe.ops.dispatch import effective_mesh

    mesh = effective_mesh(mesh)
    if spec is not None and mesh is not None:
        full = tuple(spec) + (None,) * (x.ndim - len(tuple(spec)))
        if full[-1] is not None:
            raise ValueError(f"spec {spec} must leave the feature axis unsharded")
        spec = P(*full)
        n_shards, divisible = _spec_shard_info(mesh, spec, x.shape)
        shardable = divisible and n_shards > 1
    else:
        axes, n_shards, shardable = batch_sharding_info(
            mesh, batch_axes, lead[0] if lead else 0
        )
        spec = P(axes, *([None] * (x.ndim - 1)))
    from tpuframe.ops.registry import shape_class

    interpret = resolve_interpret(
        interpret, shardable, op="layer_norm",
        shape_class=shape_class(d=x.shape[-1]),
    )
    if interpret is None:
        return layer_norm_reference(x, scale, bias, eps)

    def run(xs, s, b):
        flat = xs.reshape(-1, xs.shape[-1])
        return _fused(flat, s, b, eps, interpret).reshape(xs.shape)

    if shardable and n_shards > 1:
        return shard_map(
            run,
            mesh=mesh,
            in_specs=(spec, P(None), P(None)),
            out_specs=spec,
            check_vma=False,
        )(x, scale, bias)
    return run(x, scale, bias)


class FusedLayerNorm(nn.Module):
    """flax LayerNorm drop-in backed by :func:`fused_layer_norm`.

    Parameter names/shapes match ``nn.LayerNorm`` (``scale``/``bias``,
    (D,), f32), so checkpoints are interchangeable; on non-TPU backends
    the call lowers to the identical jnp reference, so swapping
    implementations never changes numerics.

    ``use_mesh=True`` (default) looks up the runtime mesh and runs the
    kernel per shard — batch over (data, fsdp) and, for (B, L, D)
    inputs, sequence over the seq axis, so it engages on exactly the
    multi-chip configurations that matter.  Set ``use_mesh=False`` when
    the module already runs inside a ``shard_map`` (e.g. the GPipe
    pipeline), where opening another one is invalid.
    """

    epsilon: float = 1e-6
    dtype: object = jnp.float32
    use_mesh: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        mesh = spec = None
        if self.use_mesh and not self.is_initializing():
            try:
                mesh = current_runtime(auto_init=False).mesh
            except RuntimeError:
                mesh = None
            if mesh is not None and x.ndim >= 2:
                lead = [(DATA_AXIS, FSDP_AXIS)]
                if x.ndim >= 3:
                    lead.append(SEQUENCE_AXIS)
                lead += [None] * (x.ndim - 1 - len(lead))
                spec = P(*lead, None)
        return fused_layer_norm(
            x, scale, bias, eps=self.epsilon, mesh=mesh, spec=spec
        ).astype(self.dtype)
