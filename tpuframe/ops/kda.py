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
product of two row-scaled operands; on a diagonal sub-block `_prepare`
takes the differences element by element (`_diagonal`, which computes its
decays again in the backward pass and keeps no (16, 16, dk) array a
sub-block).  No (chunks, C, C, dk) array is made.

:func:`_prepare` is the chunk-local part as XLA's batched products over every
chunk at once, differentiated by autodiff (the solve's transpose is
`ops.gated_delta._solved`'s ``-T^T dT T^T``): what the scan schedule runs, and
the oracle of the kernels ``tpuframe_kdachunk_fwd`` / ``_again`` / ``_bwd``,
which run it wherever the pass's kernels run.  Their grid step is a head's
block of `_LOCAL_CHUNKS` chunks (rows, heads and blocks all parallel) and a
loop over them in which a chunk's arrays never leave VMEM: ``c`` (the product
with the triangle of ones), the scaled keys of the eight sub-block products,
``A``, ``M``, the seven levels of `ops.gated_delta._solve`; the model's own
rows come in, the parts leave a head's rows together and ``T`` once in
float32 (``_again``, the backward pass's recomputation, is handed it and
skips the solve).  Inside a diagonal sub-block the kernels take no sum over
the key width a column (a lane reduction a row and column): they cut the
sub-block again the way the chunk was cut, into halves, quarters, eighths
and single rows, each level's lower left quarters one product of two scaled
float32 operands through the first row ``R`` of the second half, ``exp(c_i -
c_R) * exp(c_R - c_j)`` with ``j < R <= i``, float32 whole (`_halves`); ``c``
at a block's first row is a select between ``c`` and itself rolled down the
sublanes (`_first_rows`), and the transpose's sum over a block the same
rolls (`_sums_at`).  ``_bwd`` is the transpose written by hand: it computes
the decays again and none of the forward pass's products (``d beta`` through
``A = beta K K'`` is a scaled operand times its own cotangent, summed along a
row), and the reverse running sum from ``d c`` to ``d g`` is its last product.

The pass over the chunks runs as the kernels ``tpuframe_kda_fwd`` /
``tpuframe_kda_bwd`` wherever `resolve_interpret` lets kernels run and the
heads are whole lanes: `ops.gated_delta`'s pass with the state held
TRANSPOSED in VMEM, (dv, dk), so that ``gamma`` is a row of lanes that scales
its columns and ``d gamma`` a sum down its rows; heads and rows on the grid's
parallel axes, the chunks along an ``arbitrary`` axis.  :func:`kda_chunked`
runs `_prepare` and the same pass as a ``lax.scan``: what a CPU and every
call the engage rule turns away run.  (The chunk-local kernels' names start
with ``tpuframe_kdachunk_`` so that what reads the pass alone, the names that
start with ``tpuframe_kda_``, goes on reading the pass alone.)

Backward is a ``custom_vjp`` that keeps one state a chunk boundary, the
solve's ``T`` and the op's five inputs, computes the other chunk-local
arrays again, runs the pass in reverse and hands its cotangents to the
chunk-local part's transpose (the kernel's, or autodiff's of `_prepare`).
Products take operands in the inputs' dtype and accumulate in float32; the
decays, the solve and the state are float32.
"""

from __future__ import annotations

import functools
import math

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
    _LOCAL_CHUNKS,
    _NT,
    _STEP_CHUNKS,
    _TN,
    _VMEM_BYTES,
    _col,
    _dot,
    _inv_unit_lower,
    _iotas,
    _mm,
    _model_rows,
    _params,
    _row,
    _rows_again,
    _solve,
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


# -- inside a chunk: the kernels ------------------------------------------------
#: half the side of the blocks a diagonal sub-block is cut into, level by level
_HALVES = (8, 4, 2, 1)


def _first_rows(c, rows):
    """``c`` (C, dk) at the first row of each row's aligned block of 2, 4, 8
    and `_SUB` rows, by side: a select between the array and itself rolled
    down the sublanes a level, exact."""
    out = {1: c}
    t = 1
    while t < _SUB:
        c = jnp.where((rows & t) != 0, pltpu.roll(c, t, 0), c)
        t *= 2
        out[t] = c
    return out


def _halves(c, first, rows, s):
    """The decays of one level of a diagonal sub-block: its aligned blocks of
    ``2 s`` rows factor through ``R``, the first row of their second half:
    ``up = exp(c_i - c_R)`` on the second half's rows, ``down = exp(c_R -
    c_j)`` on the first half's, 0 elsewhere; both differences are <= 0."""
    second = (rows & s) != 0
    at_r = jnp.where(second, first[s], pltpu.roll(first[s], _CHUNK - s, 0))
    return (jnp.exp(jnp.where(second, c - at_r, -jnp.inf)),
            jnp.exp(jnp.where(second, -jnp.inf, at_r - c)))


def _same_block(row, col, s):
    """(C, C): row and column in one aligned block of ``2 s`` positions."""
    return ((row ^ col) >> s.bit_length()) == 0


def _sums_at(x, side, at, rows):
    """The sums of ``x`` (C, dk) over each aligned block of ``side`` rows,
    written at the block's row ``at``, zeros elsewhere: the transpose of
    reading a block's row ``at`` at every one of its rows."""
    t = 1
    while t < side:
        x = x + pltpu.roll(x, _CHUNK - t, 0)
        t *= 2
    if at:
        x = pltpu.roll(x, at, 0)
    return jnp.where((rows & (side - 1)) == at, x, 0.0)


def _before(x, dtype=jnp.float32):
    """A chunk's first rows ``x`` as ``dtype``, zeros behind them: (C, dk)."""
    return jnp.concatenate(
        [x.astype(dtype), jnp.zeros((_CHUNK - x.shape[0], x.shape[1]), dtype)], axis=0)


def _sub(x, s):
    return x[s * _SUB:(s + 1) * _SUB]


def _chunk_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref, j, row, col):
    """Chunk ``j`` of the block: where its rows lie, ``q``, ``k``, ``v`` in
    float32, ``beta`` down the rows (C, 1), ``c`` (the running sum of ``g``
    down the chunk as the product with the triangle of ones, float32 whole),
    the iota of ``c``'s rows and `_first_rows` of ``c``."""
    at = pl.ds(pl.multiple_of(j * _CHUNK, _CHUNK), _CHUNK)
    qf, kf, vf = (ref[0, at, :].astype(jnp.float32) for ref in (q_ref, k_ref, v_ref))
    c = _dot((row >= col).astype(jnp.float32), g_ref[0, at, :])
    rows = lax.broadcasted_iota(jnp.int32, c.shape, 0)
    return at, qf, kf, vf, _col(beta_ref[0, 0, j], row == col), c, rows, _first_rows(c, rows)


def _local_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs, chunks, solve):
    """`_prepare` for ``chunks`` chunks of one head, a chunk's (C, C) arrays
    in VMEM from ``g``'s running sum to ``U`` and ``W``.  ``solve`` False is
    the entry that is handed ``T``.

    Off the diagonal sub-blocks ``A`` and ``M`` are `_prepare`'s products
    (operands in the inputs' dtype, scaled through the row's sub-block's
    first row).  A diagonal sub-block is cut the same way again, into
    halves, quarters, eighths and single rows: at each level a block's lower
    left quarter is one product of its second half's rows scaled by
    ``exp(c_i - c_R)`` with its first half's keys scaled by ``exp(c_R -
    c_j)``, float32 operands whole, one product a level for the whole chunk
    under the blocks' mask; and the diagonal itself decays by nothing."""
    if solve:
        u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref, t_ref = refs
    else:
        t_ref, u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref = refs
    dtype = v_ref.dtype
    row, col = _iotas()
    eye = row == col

    def chunk(j, carry):
        at, qf, kf, vf, beta, c, rows, first = _chunk_inputs(
            q_ref, k_ref, v_ref, g_ref, beta_ref, j, row, col)
        last = c[_CHUNK - 1:]
        # the rows whose products are asked for: q's for M, and k's for A
        # where it is solved (their products lie before q's)
        left = (kf, qf) if solve else (qf,)
        # off the diagonal sub-blocks: a sub-block's rows against the keys before it
        up = jnp.exp(c - first[_SUB])
        scaled = [(x * up).astype(dtype) for x in left]
        off = [jnp.zeros((len(left) * _SUB, _CHUNK), jnp.float32)]
        for s in range(1, _CHUNK // _SUB):
            km = _before(kf[:s * _SUB] * jnp.exp(c[s * _SUB:s * _SUB + 1] - c[:s * _SUB]), dtype)
            off.append(_dot(jnp.concatenate([_sub(x, s) for x in scaled], axis=0), km, _NT))
        inner = [jnp.concatenate([x[i * _SUB:(i + 1) * _SUB] for x in off], axis=0)
                 for i in range(len(left))]
        # the diagonal sub-blocks, level by level, and the diagonal
        for s in _HALVES:
            up, down = _halves(c, first, rows, s)
            x = _dot(jnp.concatenate([x * up for x in left], axis=0), kf * down, _NT)
            same = _same_block(row, col, s)
            inner = [a + jnp.where(same, x[i * _CHUNK:(i + 1) * _CHUNK], 0.0)
                     for i, a in enumerate(inner)]
        m = inner[-1] + jnp.where(eye, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
        if solve:
            t = _solve(jnp.where(row > col, beta * inner[0], 0.0), row, col)
            t_ref[0, 0, j] = t
        else:
            t = t_ref[0, 0, j]
        tb, ec = t.astype(dtype), jnp.exp(c)
        u_ref[0, 0, at, :] = _dot(tb, (beta * vf).astype(dtype)).astype(dtype)
        w_ref[0, 0, at, :] = _dot(tb, (beta * ec * kf).astype(dtype)).astype(dtype)
        qe_ref[0, 0, at, :] = (qf * ec).astype(dtype)
        kd_ref[0, 0, at, :] = (kf * jnp.exp(last - c)).astype(dtype)
        m_ref[0, 0, at, :] = m.astype(dtype)
        gamma_ref[0, 0, j] = jnp.exp(last)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _local_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, du_ref, dw_ref, dqe_ref,
                      dkd_ref, dm_ref, dgamma_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      *, chunks):
    """The transpose of `_local_fwd_kernel`, a chunk at a time.  The decays
    are computed again; no product of the forward pass is: the cotangent of
    ``beta`` through ``A = beta K K'`` is the rows' sum of a scaled operand
    times its own cotangent before ``beta`` scales it."""
    dtype = v_ref.dtype
    row, col = _iotas()
    eye = row == col
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)  # noqa: E731
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)  # noqa: E731

    def chunk(j, carry):
        at, qf, kf, vf, beta, c, rows, first = _chunk_inputs(
            q_ref, k_ref, v_ref, g_ref, beta_ref, j, row, col)
        last = c[_CHUNK - 1:]
        t = t_ref[0, 0, j]
        tb, ec, el = t.astype(dtype), jnp.exp(c), jnp.exp(last - c)
        # U = T (B V), W = T (B K e^c): into T, and through the inverse into A
        du, dw = du_ref[0, 0, at, :], dw_ref[0, 0, at, :]
        bke = beta * ec * kf
        d_t = _dot(du, (beta * vf).astype(dtype), _NT) + _dot(dw, bke.astype(dtype), _NT)
        d_a = jnp.where(row > col, -_dot(_dot(t, d_t, _TN), t, _NT), 0.0)
        d_bv, d_bke = _dot(tb, du, _TN), _dot(tb, dw, _TN)
        dv_ref[0, at, :] = (beta * d_bv).astype(dtype)
        d_beta = rowsum(d_bv * vf) + rowsum(d_bke * ec * kf)
        # Qe = Q e^c, Kd = K exp(c_C - c), gamma = exp(c_C): c_C is the last row's c
        d_qe = dqe_ref[0, 0, at, :].astype(jnp.float32)
        d_kd = dkd_ref[0, 0, at, :].astype(jnp.float32)
        z = d_kd * kf * el
        d_last = colsum(z) + dgamma_ref[0, 0, j] * jnp.exp(last)
        dq = d_qe * ec
        dk = beta * ec * d_bke + d_kd * el
        dc = (bke * d_bke + d_qe * qf * ec - z
              + jnp.where(rows == _CHUNK - 1, d_last, 0.0))
        # A = beta KK' under the diagonal, M = QK' on and under it; the diagonal
        d_m = dm_ref[0, 0, at, :].astype(jnp.float32)
        d_a_rows = beta * d_a
        on = rowsum(jnp.where(eye, d_m, 0.0))
        dq, dk = dq + on * kf, dk + on * qf
        # off the diagonal sub-blocks
        up = jnp.exp(c - first[_SUB])
        ku, qu = kf * up, qf * up
        d_ku = [jnp.zeros((_SUB, ku.shape[1]), jnp.float32)]
        d_qu, d_beta_sub = list(d_ku), [jnp.zeros((_SUB, 1), jnp.float32)]
        for s in range(1, _CHUNK // _SUB):
            down = jnp.exp(c[s * _SUB:s * _SUB + 1] - c[:s * _SUB])
            kms = kf[:s * _SUB] * down
            km = _before(kms, dtype)
            lhs = jnp.concatenate([_sub(ku, s), _sub(qu, s)], axis=0).astype(dtype)
            d_x = jnp.concatenate([_sub(d_a, s), _sub(d_m, s)], axis=0).astype(dtype)
            e = _dot(d_x, km)                                          # (2 S, dk)
            d_beta_sub.append(rowsum(lhs[:_SUB].astype(jnp.float32) * e[:_SUB]))
            d_ku.append(_sub(beta, s) * e[:_SUB])
            d_qu.append(e[_SUB:])
            d_x = jnp.concatenate([_sub(d_a_rows, s), _sub(d_m, s)], axis=0).astype(dtype)
            d_km = _dot(d_x, lhs, _TN)[:s * _SUB]                      # (s S, dk)
            x = d_km * kms
            dk = dk + _before(d_km * down)
            dc = dc - _before(x) + jnp.where(rows == s * _SUB, colsum(x), 0.0)
        d_ku, d_qu = jnp.concatenate(d_ku, axis=0), jnp.concatenate(d_qu, axis=0)
        d_beta = d_beta + jnp.concatenate(d_beta_sub, axis=0)
        dk, dq = dk + d_ku * up, dq + d_qu * up
        x = d_ku * ku + d_qu * qu
        dc = dc + x - _sums_at(x, _SUB, 0, rows)
        # the diagonal sub-blocks, level by level
        for s in _HALVES:
            up, down = _halves(c, first, rows, s)
            ku, qu, kb = kf * up, qf * up, kf * down
            same = _same_block(row, col, s)
            g_a, g_m = jnp.where(same, d_a, 0.0), jnp.where(same, d_m, 0.0)
            e = _dot(jnp.concatenate([g_a, g_m], axis=0), kb)
            d_beta = d_beta + rowsum(ku * e[:_CHUNK])
            d_ku, d_qu = beta * e[:_CHUNK], e[_CHUNK:]
            d_kb = _dot(jnp.concatenate([beta * g_a, g_m], axis=0),
                        jnp.concatenate([ku, qu], axis=0), _TN)
            dk, dq = dk + d_ku * up + d_kb * down, dq + d_qu * up
            # the cotangent of c_i - c_R on the second half's rows, less that
            # of c_R - c_j on the first half's
            x = d_ku * ku + d_qu * qu - d_kb * kb
            dc = dc + x - _sums_at(x, 2 * s, s, rows)
        dq_ref[0, at, :] = dq.astype(dtype)
        dk_ref[0, at, :] = dk.astype(dtype)
        # the running sum's transpose: the sum from a row to the chunk's end
        dg_ref[0, at, :] = _dot((row >= col).astype(jnp.float32), dc, _TN)
        dbeta_ref[0, 0, j] = _row(d_beta, eye)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _local_call(kernel, name, q, v, interpret):
    """-> ``pl.pallas_call`` with one chunk-local kernel on the grid (rows,
    heads, blocks of chunks), every axis parallel, and its block specs:
    ``model(width)`` a head's lanes of the model's rows (B, L, H * width),
    ``head(width)`` a head's rows of a (B, H, L, width) array, ``chunk(a,
    b)`` a head's chunks of a (B, H, N, a, b) array."""
    b, length, h, _ = q.shape
    n = length // _CHUNK
    chunks = math.gcd(n, _LOCAL_CHUNKS)
    rows = chunks * _CHUNK
    model = lambda width: pl.BlockSpec((1, rows, width), lambda b, h, i: (b, i, h))  # noqa: E731
    head = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, rows, width), lambda b, h, i: (b, h, i, 0))
    chunk = lambda *block: pl.BlockSpec(  # noqa: E731
        (1, 1, chunks) + block, lambda b, h, i: (b, h, i, 0, 0))
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, chunks=chunks),
        grid=(b, h, n // chunks),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name=name)
    return call, model, head, chunk


def _along_lanes(beta):
    """A number a position along the lanes: (B, L, H) -> (B, H, N, 1, C) float32."""
    b, length, h = beta.shape
    return jnp.transpose(beta.astype(jnp.float32).reshape(b, length // _CHUNK, _CHUNK, h),
                         (0, 3, 1, 2))[..., None, :]


def _local_parts(q, k, v, g, beta, t, interpret):
    """`_prepare` as a kernel: with ``t`` None the one that solves (-> the
    parts and ``T``), else the one that is handed ``T`` (-> the parts)."""
    b, length, h, dk = q.shape
    dv, n = v.shape[-1], length // _CHUNK
    call, model, head, chunk = _local_call(
        functools.partial(_local_fwd_kernel, solve=t is None),
        "tpuframe_kdachunk_" + ("fwd" if t is None else "again"), q, v, interpret)
    part = lambda width: jax.ShapeDtypeStruct((b, h, length, width), v.dtype)  # noqa: E731
    out = (part(dv), part(dk), part(dk), part(dk), part(_CHUNK),
           jax.ShapeDtypeStruct((b, h, n, 1, dk), jnp.float32))
    out_specs = (head(dv), head(dk), head(dk), head(dk), head(_CHUNK), chunk(1, dk))
    solved = jax.ShapeDtypeStruct((b, h, n, _CHUNK, _CHUNK), jnp.float32)
    own = [model(dk), model(dk), model(dv), model(dk), chunk(1, _CHUNK)]
    args = (*_model_rows(q, k, v, g.astype(jnp.float32)), _along_lanes(beta))
    if t is None:
        *parts, gamma, t = call(out_shape=out + (solved,), in_specs=own,
                                out_specs=out_specs + (chunk(_CHUNK, _CHUNK),))(*args)
        return (*parts, gamma[..., 0, :]), t
    *parts, gamma = call(out_shape=out, in_specs=own + [chunk(_CHUNK, _CHUNK)],
                         out_specs=out_specs)(*args, t)
    return (*parts, gamma[..., 0, :])


# Jitted like the pass's callers: one trace and one lowering for a model's layers.
@functools.partial(jax.jit, static_argnums=(5,))
def _pallas_local_fwd(q, k, v, g, beta, interpret):
    return _local_parts(q, k, v, g, beta, None, interpret)


@functools.partial(jax.jit, static_argnums=(6,))
def _pallas_local_again(q, k, v, g, beta, t, interpret):
    return _local_parts(q, k, v, g, beta, t, interpret)


@functools.partial(jax.jit, static_argnums=(7,))
def _pallas_local_bwd(q, k, v, g, beta, t, d_parts, interpret):
    """The cotangents of the op's five inputs from those of the parts."""
    *d_rows, d_gamma = d_parts
    dk, dv = q.shape[-1], v.shape[-1]
    call, model, head, chunk = _local_call(
        _local_bwd_kernel, "tpuframe_kdachunk_bwd", q, v, interpret)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    rows = _model_rows(q, k, v, g.astype(jnp.float32))
    numbers = _along_lanes(beta)
    own = [model(dk), model(dk), model(dv), model(dk), chunk(1, _CHUNK)]
    d_q, d_k, d_v, d_g, d_beta = call(
        out_shape=(*(like(a) for a in rows), like(numbers)),
        in_specs=[*own, chunk(_CHUNK, _CHUNK), *(head(a.shape[-1]) for a in d_rows),
                  chunk(1, dk)],
        out_specs=tuple(own),
    )(*rows, numbers, t, *d_rows, d_gamma[..., None, :])
    d_beta = jnp.transpose(d_beta[..., 0, :], (0, 2, 3, 1)).reshape(beta.shape)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_g.reshape(g.shape).astype(g.dtype), d_beta.astype(beta.dtype))


# -- the op ---------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    """The chunked schedule over whole chunks; ``interpret`` None runs the
    pass over the chunks as a scan, else as the kernels."""
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    if interpret is None:
        parts, t = _prepare(q, k, v, g, beta)
        o, states = _scan_fwd(parts)
    else:
        parts, t = _pallas_local_fwd(q, k, v, g, beta, interpret)
        o, states = _pallas_fwd(parts, interpret)
    # (B, H, L, dv) -> the model's (B, L, H, dv)
    return jnp.swapaxes(o, 1, 2).astype(v.dtype), (q, k, v, g, beta, t, states)


def _rule_bwd(interpret, residuals, do):
    *inputs, t, states = residuals
    if interpret is None:
        # the chunk-local arrays again (all but the solve, whose result was
        # kept), and only now: without the barrier XLA sees the forward
        # pass's own computation of them, merges the two and keeps every
        # intermediate of every layer alive across the step
        inputs, t, do = lax.optimization_barrier((inputs, t, do))
        parts, transpose = jax.vjp(lambda *a: _prepare(*a, t=t)[0], *inputs)
        return transpose(tuple(_scan_bwd(parts, states, jnp.swapaxes(do, 1, 2))))
    # a kernel of its own computes them again: nothing for XLA to merge, no barrier
    parts = _pallas_local_again(*inputs, t, interpret)
    d_parts = _pallas_bwd(parts, states, jnp.swapaxes(do, 1, 2), interpret)
    return _pallas_local_bwd(*inputs, t, d_parts, interpret)


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
        engaged_attrs={"pass": "kernels", "chunk_local": "kernels", "sub_block": _SUB})
    run = functools.partial(_padded, interpret=interpret)
    if interpret is not None and shardable and n_shards > 1:
        row, head = P(axes, None, None, None), P(axes, None, None)
        return shard_map(run, mesh=mesh, in_specs=(row, row, row, row, head),
                         out_specs=row, check_vma=False)(q, k, v, g, beta)
    return run(q, k, v, g, beta)
