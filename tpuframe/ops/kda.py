"""Kimi Delta Attention's rule: the gated delta rule with a decay a key channel.

`ops.gated_delta`'s recurrence with ``g`` a vector over the key width, so the
decay scales the state's ROWS, one factor a key channel (per head and
position; ``q``, ``k`` L2-normalised, ``g <= 0``, ``beta`` in (0, 1))::

    S'_t = diag(exp(g_t)) S_{t-1}
    S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T
    o_t  = S_t^T q_t                                  (S_0 = 0, float32)

:func:`kda_reference` is those three lines as a scan over positions: the
oracle, and what ``init`` runs.  Everything else runs the **chunked
schedule**, which equals the recurrence exactly.  In chunks of `_CHUNK`
(128, `ops.gated_delta`'s, and for its reasons: whole 128 x 128 tiles of the
MXU, ``M`` a row of 128 lanes, half as many steps of the pass as the
source's 64) positions, with ``c_i`` in R^dk the sum of ``g`` from the
chunk's first position to its i-th::

    A_ij = beta_i sum_d k_id k_jd exp(c_id - c_jd)    (j < i)
    M_ij =        sum_d q_id k_jd exp(c_id - c_jd)    (j <= i)

``T = (I + A)^-1``, ``U = T B V``, ``W = T (B K * exp(c))``, ``Qe = Q *
exp(c)``, ``Kd = K * exp(c_C - c)``, ``gamma = exp(c_C)`` in R^dk, and over
the chunks, one after another, the state in float32::

    V' = U - W S;   O = Qe S + M V';   S <- diag(gamma) S + Kd^T V'

The decay sits INSIDE ``A``'s and ``M``'s inner products, so they are no
product under an element-wise (C, C) mask as the scalar rule's are.  **Every
decay stays ``exp`` of a difference that is <= 0, never a quotient of two
exponentials** (a channel's ``g`` reaches -20 a position): a chunk is cut
into sub-blocks of `_SUB` (16) rows; off the diagonal sub-blocks ``A`` and
``M`` factor through the boundary ``r``, the first row of the row's
sub-block, ``exp(c_i - c_r) * exp(c_r - c_j)`` with ``j < r <= i``: one
product of two row-scaled operands; on a diagonal sub-block the differences
are taken element by element (`_diagonal`, which computes its decays again
in the backward pass and keeps no (16, 16, dk) array a sub-block).  No
(chunks, C, C, dk) array is made.

:func:`_prepare` is the chunk-local part as XLA's batched products over every
chunk at once, differentiated by autodiff (the solve's transpose is
`ops.gated_delta._solved`'s ``-T^T dT T^T``).  The pass over the chunks runs
as the kernels ``tpuframe_kda_fwd`` / ``tpuframe_kda_bwd`` wherever
`resolve_interpret` lets kernels run and the heads are whole lanes:
`ops.gated_delta`'s pass with the state held TRANSPOSED in VMEM, (dv, dk), so
that ``gamma`` is a row of lanes that scales its columns and ``d gamma`` a
sum down its rows; heads and rows on the grid's parallel axes, the chunks
along an ``arbitrary`` axis.  :func:`kda_chunked` runs the same pass as a
``lax.scan``: what a CPU and every call the engage rule turns away run.

Backward is a ``custom_vjp`` that keeps one state a chunk boundary, the
solve's ``T`` and the op's five inputs, computes the other chunk-local
arrays again, runs the pass in reverse and hands its cotangents to the
chunk-local part's transpose.  Products take operands in the inputs' dtype
and accumulate in float32; the decays, the solve and the state are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpuframe.ops.dispatch import batch_sharding_info, pad_to, resolve_interpret
from tpuframe.ops.gated_delta import (
    _CHUNK,
    _HI,
    _LANES,
    _NT,
    _STEP_CHUNKS,
    _TN,
    _dot,
    _inv_unit_lower,
    _mm,
    _params,
    _rows_again,
    _solved,
    _specs,
    _split,
    _step_rows,
    chunks_walked,
)
from tpuframe.ops.registry import shape_class

__all__ = ["kda", "kda_chunked", "kda_reference", "chunks_walked"]

#: rows of a diagonal sub-block, whose decays are taken element by element
#: (the source's kernels' own)
_SUB = 16


def kda_reference(q, k, v, g, beta):
    """jnp oracle: ``q``, ``k``, ``g`` (B, L, H, dk), ``v`` (B, L, H, dv),
    ``beta`` (B, L, H) -> (B, L, H, dv).  The recurrence position by
    position, float32."""
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HI)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * err, precision=_HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    b, _, h, dv = v.shape
    s0 = jnp.zeros((b, h, q.shape[-1], dv), jnp.float32)
    _, out = lax.scan(step, s0, (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# -- inside a chunk: plain XLA, every chunk at once ------------------------------
def _decays(c):
    """``exp(c_a - c_b)`` on and under the diagonal of a sub-block, 0 over
    it: (..., S, dk) -> (..., S, S, dk)."""
    i = jnp.arange(c.shape[-2])
    on_or_under = (i[:, None] >= i[None, :])[..., None]
    return jnp.exp(jnp.where(on_or_under, c[..., :, None, :] - c[..., None, :, :], -jnp.inf))


@jax.custom_vjp
def _diagonal(q, k, c):
    """The diagonal sub-blocks, the decays element by element: ``sum_d k_ad
    k_bd exp(c_ad - c_bd)`` and the same with ``q_ad``, for ``b <= a``; (...,
    S, dk) float32 -> two (..., S, S).  Written with a backward pass of its
    own that computes the decays again: autodiff would keep a (S, S, dk)
    array a sub-block, a GiB a layer at the published sizes."""
    ke = _decays(c) * k[..., None, :, :]
    return (jnp.sum(k[..., :, None, :] * ke, axis=-1),
            jnp.sum(q[..., :, None, :] * ke, axis=-1))


def _diagonal_fwd(q, k, c):
    return _diagonal(q, k, c), (q, k, c)


def _diagonal_bwd(residuals, cotangents):
    q, k, c = residuals
    d_kk, d_qk = (d[..., None] for d in cotangents)
    e = _decays(c)
    ke = e * k[..., None, :, :]
    # the row's side of each product, then the column's
    dq, dk_row = jnp.sum(d_qk * ke, axis=-2), jnp.sum(d_kk * ke, axis=-2)
    dk_col = jnp.sum((d_kk * k[..., :, None, :] + d_qk * q[..., :, None, :]) * e, axis=-3)
    # c_a enters a row's terms with +1 and a column's with -1
    return dq, dk_row + dk_col, q * dq + k * (dk_row - dk_col)


_diagonal.defvjp(_diagonal_fwd, _diagonal_bwd)


def _block_diagonal(d):
    """(..., nsub, S, S) -> (..., C, C): the sub-blocks along the diagonal,
    zeros elsewhere.  Each sub-block's rows are padded to the chunk's width
    where they lie: a product with an identity over the sub-blocks would make
    a (nsub, S, nsub, S) array, whose 16-wide minor axes cost the chip eight
    times their bytes (2.7 + 1.6 ms a layer in the forward pass alone; my chip
    run, PR 50)."""
    nsub, s = d.shape[-3], d.shape[-1]
    lead = [(0, 0)] * (d.ndim - 2)
    return jnp.concatenate(
        [jnp.pad(d[..., i, :, :], lead + [(i * s, (nsub - 1 - i) * s)]) for i in range(nsub)],
        axis=-2)


def _chunks(a):
    """(B, L, H, ...) -> (B, H, N, C, ...)."""
    b, length, h = a.shape[:3]
    return jnp.moveaxis(a.reshape((b, length // _CHUNK, _CHUNK, h) + a.shape[3:]), 3, 1)


def _prepare(q, k, v, g, beta, t=None):
    """The chunk-local arrays of the schedule, for every chunk at once, a
    head's rows together.  ``L`` is whole chunks.  -> ``u`` (B, H, L, dv),
    ``w``, ``qe``, ``kd`` (B, H, L, dk) and ``m`` (B, H, L, C) in the
    inputs' dtype, ``gamma`` (B, H, N, dk) float32, and the solve's ``T`` (B,
    H, N, C, C) float32, which a caller that has it from before hands back
    as ``t``."""
    b, length, h, _ = q.shape
    n, nsub, dtype = length // _CHUNK, _CHUNK // _SUB, v.dtype
    rows = lambda a: a.astype(dtype).reshape(b, h, length, -1)  # noqa: E731
    sub = lambda a: a.reshape(a.shape[:3] + (nsub, _SUB) + a.shape[4:])  # noqa: E731
    square = lambda a: a.reshape(b, h, n, _CHUNK, _CHUNK)  # noqa: E731
    q, k, v = (_chunks(a).astype(jnp.float32) for a in (q, k, v))
    beta = _chunks(beta.astype(jnp.float32))[..., None]              # (B, H, N, C, 1)
    # the running sum of g along a chunk as a product with a triangle of ones
    # (float32 whole): XLA's cumsum is a reduce-window, 1.1 ms a layer each way
    i = jnp.arange(_CHUNK)
    ones = (i[:, None] >= i[None, :]).astype(jnp.float32)
    c = jnp.einsum("ij,bhnjd->bhnid", ones, _chunks(g.astype(jnp.float32)),
                   precision=_HI)                                    # (B, H, N, C, dk)
    last = c[..., -1:, :]
    # off the diagonal sub-blocks: through c at the row's sub-block's first
    # row r: rows scaled by exp(c_i - c_r), the columns before r by exp(c_r - c_j)
    cs = sub(c)
    r = cs[..., :1, :]                                               # (B, H, N, nsub, 1, dk)
    up = jnp.exp(cs - r)
    before = (jnp.arange(_CHUNK) < _SUB * jnp.arange(nsub)[:, None])[..., None]
    down = jnp.exp(jnp.where(before, r - c[..., None, :, :], -jnp.inf))
    km = (k[..., None, :, :] * down).astype(dtype)                   # (B, H, N, nsub, C, dk)
    off = lambda x: square(_mm("bhnsad,bhnsjd->bhnsaj", (sub(x) * up).astype(dtype), km))  # noqa: E731
    kk, qk = _diagonal(sub(q), sub(k), cs)
    a = jnp.where(i[:, None] > i[None, :], beta * (off(k) + _block_diagonal(kk)), 0.0)
    m = off(q) + _block_diagonal(qk)
    t = _inv_unit_lower(a) if t is None else _solved(a, t)
    ec, tb = jnp.exp(c), t.astype(dtype)
    u = rows(_mm("bhnij,bhnjd->bhnid", tb, (beta * v).astype(dtype)))
    w = rows(_mm("bhnij,bhnjd->bhnid", tb, (beta * ec * k).astype(dtype)))
    parts = (u, w, rows(q * ec), rows(k * jnp.exp(last - c)), rows(m),
             jnp.exp(last[..., 0, :]))
    return parts, t


# -- over the chunks: the scan schedule -----------------------------------------
def _chunk_first(parts):
    *rows, gamma = parts
    return (*(_split(a) for a in rows), jnp.moveaxis(gamma, 2, 0))


def _scan_fwd(parts):
    """-> the outputs (B, H, L, dv) and the state at every chunk's start
    (B, H, N, dk, dv) float32."""
    u, w, qe, kd, m, gamma = _chunk_first(parts)
    dtype = u.dtype

    def body(s, xs):
        u, w, qe, kd, m, gamma = xs
        sb = s.astype(dtype)
        vp = (u.astype(jnp.float32) - _mm("bhck,bhkv->bhcv", w, sb)).astype(dtype)
        o = _mm("bhck,bhkv->bhcv", qe, sb) + _mm("bhij,bhjv->bhiv", m, vp)
        return gamma[..., None] * s + _mm("bhck,bhcv->bhkv", kd, vp), (o, s)

    _, b, h, _, dv = u.shape
    _, (o, states) = lax.scan(body, jnp.zeros((b, h, w.shape[-1], dv), jnp.float32),
                              (u, w, qe, kd, m, gamma))
    return _rows_again(o), jnp.moveaxis(states, 0, 2)


def _scan_bwd(parts, states, do):
    """The transposes of the pass's three lines, last chunk first."""
    u, w, qe, kd, m, gamma = _chunk_first(parts)
    dtype = u.dtype
    do = _split(do.astype(dtype))

    def body(ds, xs):
        u, w, qe, kd, m, gamma, s, do = xs
        sb, dsb = s.astype(dtype), ds.astype(dtype)
        vp = (u.astype(jnp.float32) - _mm("bhck,bhkv->bhcv", w, sb)).astype(dtype)
        dvp = _mm("bhij,bhiv->bhjv", m, do) + _mm("bhck,bhkv->bhcv", kd, dsb)
        dvpb = dvp.astype(dtype)
        d_qe = _mm("bhcv,bhkv->bhck", do, sb)
        d_m = _mm("bhiv,bhjv->bhij", do, vp)
        d_kd = _mm("bhcv,bhkv->bhck", vp, dsb)
        d_gamma = jnp.sum(s * ds, axis=-1)
        d_w = -_mm("bhcv,bhkv->bhck", dvpb, sb)
        ds = (gamma[..., None] * ds + _mm("bhck,bhcv->bhkv", qe, do)
              - _mm("bhck,bhcv->bhkv", w, dvpb))
        return ds, (dvp, d_w, d_qe, d_kd, d_m, d_gamma)

    _, (*d, d_gamma) = lax.scan(
        body, jnp.zeros_like(states[:, :, 0]),
        (u, w, qe, kd, m, gamma, jnp.moveaxis(states, 2, 0), do), reverse=True)
    return (*(_rows_again(a).astype(dtype) for a in d), jnp.moveaxis(d_gamma, 0, 2))


# -- over the chunks: the kernels -----------------------------------------------
def _fwd_kernel(u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref, o_ref, states_ref, st_ref,
                *, chunks):
    """``st_ref``: the state transposed, (dv, dk); ``gamma_ref[0, 0, j]`` a
    row of dk lanes."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = u_ref.dtype
    for j in range(chunks):
        rows = pl.ds(j * _CHUNK, _CHUNK)
        st = st_ref[...]
        states_ref[0, 0, j] = st
        stb = st.astype(dtype)
        vp = (u_ref[0, 0, rows, :].astype(jnp.float32)
              - _dot(w_ref[0, 0, rows, :], stb, _NT)).astype(dtype)
        o = _dot(qe_ref[0, 0, rows, :], stb, _NT) + _dot(m_ref[0, 0, rows, :], vp)
        o_ref[0, 0, rows, :] = o.astype(o_ref.dtype)
        st_ref[...] = gamma_ref[0, 0, j] * st + _dot(vp, kd_ref[0, 0, rows, :], _TN)


def _bwd_kernel(u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref, states_ref, do_ref,
                du_ref, dw_ref, dqe_ref, dkd_ref, dm_ref, dgamma_ref, dst_ref, *, chunks):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    dtype = u_ref.dtype
    for j in reversed(range(chunks)):
        rows = pl.ds(j * _CHUNK, _CHUNK)
        st, dst = states_ref[0, 0, j], dst_ref[...]
        stb, dstb = st.astype(dtype), dst.astype(dtype)
        w, qe, kd = w_ref[0, 0, rows, :], qe_ref[0, 0, rows, :], kd_ref[0, 0, rows, :]
        m, do = m_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        vp = (u_ref[0, 0, rows, :].astype(jnp.float32) - _dot(w, stb, _NT)).astype(dtype)
        dvp = _dot(m, do, _TN) + _dot(kd, dstb, _NT)
        dvpb = dvp.astype(dtype)
        du_ref[0, 0, rows, :] = dvpb
        dw_ref[0, 0, rows, :] = (-_dot(dvpb, stb)).astype(dtype)
        dqe_ref[0, 0, rows, :] = _dot(do, stb).astype(dtype)
        dkd_ref[0, 0, rows, :] = _dot(vp, dstb).astype(dtype)
        dm_ref[0, 0, rows, :] = _dot(do, vp, _NT).astype(dtype)
        dgamma_ref[0, 0, j] = jnp.sum(st * dst, axis=0, keepdims=True)
        dst_ref[...] = gamma_ref[0, 0, j] * dst + _dot(do, qe, _TN) - _dot(dvpb, w, _TN)


# Jitted, as `ops.gated_delta`'s are: a model's layers hold one trace and one lowering.
@functools.partial(jax.jit, static_argnums=(1,))
def _pallas_fwd(parts, interpret):
    u, w, qe, kd, m, gamma = parts
    b, h, length, dv = u.shape
    dk = w.shape[-1]
    rows = _step_rows(length)
    steps, n = length // rows, length // _CHUNK
    # the state lies transposed: the lane block is dk wide, a state (dv, dk)
    row, lane, states = _specs(dv, dk, rows, steps, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=rows // _CHUNK),
        out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((b, h, n, dv, dk), jnp.float32)),
        grid=(b, h, steps),
        in_specs=[row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane],
        out_specs=(row(dv), states),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_kda_fwd",
    )(u, w, qe, kd, m, gamma[..., None, :])


@functools.partial(jax.jit, static_argnums=(3,))
def _pallas_bwd(parts, states, do, interpret):
    u, w, qe, kd, m, gamma = parts
    b, h, length, dv = u.shape
    dk = w.shape[-1]
    rows = _step_rows(length)
    steps = length // rows
    row, lane, states_spec = _specs(dv, dk, rows, steps, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    *d, d_gamma = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=rows // _CHUNK),
        out_shape=(like(u), like(w), like(qe), like(kd), like(m),
                   jax.ShapeDtypeStruct(gamma.shape[:3] + (1, dk), jnp.float32)),
        grid=(b, h, steps),
        in_specs=[row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane, states_spec, row(dv)],
        out_specs=(row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_kda_bwd",
    )(u, w, qe, kd, m, gamma[..., None, :], states, do.astype(u.dtype))
    return (*d, d_gamma[..., 0, :])


# -- the op ---------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    """The chunked schedule over whole chunks; ``interpret`` None runs the
    pass over the chunks as a scan, else as the kernels."""
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    parts, t = _prepare(q, k, v, g, beta)
    o, states = _scan_fwd(parts) if interpret is None else _pallas_fwd(parts, interpret)
    # (B, H, L, dv) -> the model's (B, L, H, dv)
    return jnp.swapaxes(o, 1, 2).astype(v.dtype), (q, k, v, g, beta, t, states)


def _rule_bwd(interpret, residuals, do):
    *inputs, t, states = residuals
    # the chunk-local arrays again (all but the solve, whose result was kept),
    # and only now: without the barrier XLA sees the forward pass's own
    # computation of them, merges the two and keeps every intermediate of
    # every layer alive across the step
    inputs, t, do = lax.optimization_barrier((inputs, t, do))
    parts, transpose = jax.vjp(lambda *a: _prepare(*a, t=t)[0], *inputs)
    do = jnp.swapaxes(do, 1, 2)
    if interpret is None:
        return transpose(tuple(_scan_bwd(parts, states, do)))
    return transpose(tuple(_pallas_bwd(parts, states, do, interpret)))


_rule.defvjp(_rule_fwd, _rule_bwd)


def _padded(q, k, v, g, beta, *, interpret):
    """The schedule on whole grid steps: a row of another length is padded
    behind with positions that neither decay nor write (``g`` and ``beta``
    0), which leave the state, and so every position before them, alone."""
    length, step = v.shape[1], _CHUNK * _STEP_CHUNKS
    pad = pad_to(length, _CHUNK if length <= step else step) - length
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    return _rule(q, k, v, g, beta, interpret)[:, :length]


def _check(q, k, v, g, beta):
    b, length, h, _ = v.shape
    if (q.shape != k.shape or q.shape[:3] != (b, length, h) or g.shape != q.shape
            or beta.shape != (b, length, h)):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, g {g.shape} are not (B, L, H, dk) beside v "
            f"{v.shape} (B, L, H, dv) and beta {beta.shape} (B, L, H)")


def kda_chunked(q, k, v, g, beta):
    """The chunked schedule with the pass over the chunks as a ``lax.scan``:
    shapes and results as :func:`kda_reference`."""
    _check(q, k, v, g, beta)
    return _padded(q, k, v, g, beta, interpret=None)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
        interpret: bool | None = None, *, mesh=None,
        batch_axes: tuple | None = None) -> jax.Array:
    """Kimi Delta Attention's rule of ``q``, ``k``, ``g`` (B, L, H, dk), ``v``
    (B, L, H, dv) and ``beta`` (B, L, H) -> (B, L, H, dv).  Differentiable in
    all five.

    ``interpret``: None = auto (the pass's kernels on a TPU, the scan
    schedule elsewhere, by `resolve_interpret`); the op's own shape rule asks
    for heads of whole lanes (``dk`` and ``dv`` multiples of 128).  On a
    ``mesh`` whose batch axes divide the rows the schedule runs per shard
    under ``shard_map`` (rows are independent).
    """
    _check(q, k, v, g, beta)
    if interpret is None and (q.shape[-1] % _LANES or v.shape[-1] % _LANES):
        return _padded(q, k, v, g, beta, interpret=None)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, v.shape[0])
    interpret = resolve_interpret(
        interpret, shardable, op="kda",
        shape_class=shape_class(l=v.shape[1], h=v.shape[2], c=_CHUNK),
        engaged_attrs={"pass": "kernels", "chunk_local": "xla", "sub_block": _SUB})
    run = functools.partial(_padded, interpret=interpret)
    if interpret is not None and shardable and n_shards > 1:
        row, head = P(axes, None, None, None), P(axes, None, None)
        return shard_map(run, mesh=mesh, in_specs=(row, row, row, row, head),
                         out_specs=row, check_vma=False)(q, k, v, g, beta)
    return run(q, k, v, g, beta)
