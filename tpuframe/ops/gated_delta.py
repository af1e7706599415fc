"""The gated delta rule (Gated DeltaNet's linear attention), chunked (Pallas).

A recurrence with one (d_k, d_v) state a value head, carried along the
sequence.  For L2-normalised queries and keys, values, a log-decay
``g <= 0`` and a write strength ``beta`` in (0, 1), per head and position::

    S'_t = exp(g_t) S_{t-1}
    S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T
    o_t  = S_t^T q_t                                  (S_0 = 0, float32)

:func:`gated_delta_reference` is those three lines as a scan over
positions: the oracle, and what ``init`` runs.  Everything else runs the
**chunked schedule**, which equals the recurrence exactly.  In chunks of
``_CHUNK`` (128) positions, with ``c_i`` the sum of ``g`` from the chunk's first
position to its i-th, ``D_ij = exp(c_i - c_j)`` on and under the diagonal,
``K``, ``Q``, ``V`` the chunk's rows and ``B = diag(beta)``:

* inside a chunk, no chunk waiting for another: ``T = (I + tril((B K K^T)
  * D, -1))^-1`` in float32 by block substitution, ``U = T B V``, ``W = T (B
  K * exp(c))``, ``Qe = Q * exp(c)``, ``Kd = K * exp(c_C - c)``, ``M =
  tril((Q K^T) * D)`` and ``gamma = exp(c_C)``.  :func:`_prepare` is that as
  plain XLA batched products over every chunk at once, differentiated by
  autodiff: what the scan schedule runs, and the oracle of the kernels
  ``tpuframe_delta_chunk_fwd`` / ``_again`` / ``_bwd``, which run it
  wherever the pass's kernels run.  Their grid step is a key head's block
  of chunks (every axis parallel) and a loop over them in which a chunk's
  (C, C) arrays, the decays, ``K K^T`` and ``Q K^T`` (made once for the key
  head's value heads), ``A``, the seven levels of the solve, never leave
  VMEM; the model's own rows come in, the parts leave a head's rows
  together, ``T`` leaves once in float32 (``_again``, the backward pass's
  recomputation, is handed it and skips the solve), and ``_bwd`` is the
  transpose written by hand (``d A = -T^T d T T^T``).  A number a position
  (``c``, ``beta``) reaches them along the lanes, (.., C), and is made
  down the rows inside;
* over the chunks, one after another, the state ``S`` in float32::

      V' = U - W S;   O = Qe S + M V';   S <- gamma S + Kd^T V'

  which is the pass the kernels ``tpuframe_gated_delta_fwd`` / ``_bwd``
  run: heads and rows on the grid's parallel axes, the chunks along an
  ``arbitrary`` axis with the state resident in VMEM, four products a
  chunk forward and ten backward.  :func:`gated_delta_chunked` runs the
  same pass as a ``lax.scan``: what a CPU and every call the engage rule
  turns away run, since the oracle's backward would keep a state a position.

Every decay is ``exp`` of a sum or of a difference taken before the
exponential (all of them <= 0), never a quotient of two exponentials: at
the published initialisation a head's ``g`` reaches -20 a position.

Backward is a ``custom_vjp`` that keeps one state a chunk boundary
(``L / _CHUNK`` of them a head), the solve's ``T`` and the op's five
inputs; it computes the other chunk-local arrays again, runs the pass in
reverse (``dS`` resident in VMEM) and hands the pass's cotangents to the
chunk-local part's own transpose (the solve's is ``-T^T dT T^T``).
Products take operands in the inputs' dtype and accumulate in float32; the
solve and its transpose (float32 operands whole), the decays and the
state are float32.
"""

from __future__ import annotations

import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpuframe.ops.dispatch import batch_sharding_info, pad_to, resolve_interpret
from tpuframe.ops.registry import shape_class

__all__ = ["gated_delta", "gated_delta_chunked", "gated_delta_reference", "chunks_walked"]

_LANES = 128
#: positions a chunk.  The source's own is 64; on the v5e 128 reads better
#: (PERF.md section 6, PR 43): the chunk-local products are then whole
#: 128 x 128 tiles of the MXU (a 64-wide product is padded to them and costs
#: as much), every product of the pass has 128 rows, and the pass is half as
#: many steps
_CHUNK = 128
#: chunks a grid step of the kernels holds
_STEP_CHUNKS = 2
_VMEM_BYTES = 32 * 2**20
_HI = lax.Precision.HIGHEST


def gated_delta_reference(q, k, v, g, beta):
    """jnp oracle: ``q``, ``k`` (B, L, Hk, dk), ``v`` (B, L, H, dv), ``g``,
    ``beta`` (B, L, H) -> (B, L, H, dv).  Key head ``h // (H / Hk)`` serves
    value head ``h``.  The recurrence position by position, float32."""
    group = v.shape[2] // q.shape[2]
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731
    q, k = (jnp.repeat(f32(a), group, axis=2) for a in (q, k))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HI)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * err, precision=_HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    b, _, h, dv = v.shape
    s0 = jnp.zeros((b, h, q.shape[-1], dv), jnp.float32)
    _, out = lax.scan(step, s0, (q, k, f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# -- inside a chunk: plain XLA, every chunk at once (the scan schedule's) --------
def _mm(spec, a, b):
    """A product that accumulates in float32; float32 operands whole."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision=_HI if a.dtype == jnp.float32 else None)


def _inv_unit_lower(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` (..., n, n), ``n`` a
    power of two, float32: block substitution from the diagonal outward.
    With ``T`` the inverse of the diagonal blocks of side ``s``, the blocks
    of side ``2 s`` have the inverse ``T - T a_s T``, ``a_s`` the part of
    ``a`` in their lower left quarters.  (The product of the factors ``I +
    (-a)^(2^i)`` is as exact on paper and cancels catastrophically where a
    chunk's keys resemble each other.)"""
    n = a.shape[-1]
    i = jnp.arange(n)
    row, col = i[:, None], i[None, :]
    t = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    s = 1
    while s < n:
        quarter = (row // (2 * s) == col // (2 * s)) & (row // s % 2 == 1) & (col // s % 2 == 0)
        a_s = jnp.where(quarter, a, 0.0)
        t = t - jnp.matmul(jnp.matmul(t, a_s, precision=_HI), t, precision=_HI)
        s *= 2
    return t


@jax.custom_vjp
def _solved(a, t):
    """``t``, which is ``(I + a)^-1`` as the forward pass computed it: the
    backward pass's stand-in for solving again.  Its transpose is that of
    the inverse, ``d a = -t^T d t t^T``: two products, where differentiating
    the substitution's levels is thirty."""
    return t


def _solved_fwd(a, t):
    return t, t


def _solved_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return -jnp.matmul(jnp.matmul(tt, dt, precision=_HI), tt, precision=_HI), jnp.zeros_like(t)


_solved.defvjp(_solved_fwd, _solved_bwd)


def _prepare(q, k, v, g, beta, t=None):
    """The chunk-local arrays of the schedule, for every chunk at once, a
    head's rows together (what a batched product gives, and what the
    kernels' blocks read).  ``L`` is whole chunks.  -> ``u`` (B, H, L, dv),
    ``w``, ``qe``, ``kd`` (B, H, L, dk) and ``m`` (B, H, L, C) in the
    inputs' dtype, ``gamma`` (B, H, N) float32, and the solve's ``T`` (B, H,
    N, C, C) float32, which a caller that has it from before hands back as
    ``t``.  A key head's rows are written out once a value head in the
    inputs' dtype (what the products read); nothing float32 is."""
    b, length, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    group, n, dtype = h // hk, length // _CHUNK, v.dtype
    # (B, L, heads, ...) -> (B, key heads, 1 or group, N, C, ...)
    chunks = lambda a, heads: jnp.moveaxis(  # noqa: E731
        a.reshape((b, n, _CHUNK, hk, heads // hk) + a.shape[3:]), (3, 4), (1, 2))
    per_head = lambda a: a.reshape((b, h) + a.shape[3:])  # noqa: E731
    stored = lambda a: per_head(jnp.broadcast_to(  # noqa: E731
        a, (b, hk, group) + a.shape[3:])).astype(dtype)
    rows = lambda a: a.astype(dtype).reshape(b, h, length, -1)  # noqa: E731
    q, k = chunks(q, hk), chunks(k, hk)
    # the keys a value head: the product over them costs less than a
    # float32 (C, C) array a key head copied out to its value heads
    kh, qh = stored(k), stored(q)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, chunks(v, h)))
    # a number a position stays (..., C): a trailing axis of one would cost
    # the chip a row of 128 lanes an element
    beta = chunks(beta.astype(jnp.float32), h)                       # (B, Hk, G, N, C)
    c = jnp.cumsum(chunks(g.astype(jnp.float32), h), axis=-1)
    last = c[..., -1:]
    i = jnp.arange(_CHUNK)
    on_or_under, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    decay = per_head(jnp.exp(jnp.where(on_or_under, c[..., :, None] - c[..., None, :], -jnp.inf)))
    kk = _mm("bhnid,bhnjd->bhnij", kh, kh)
    a = jnp.where(strict, per_head(beta)[..., None] * kk * decay, 0.0)  # (B, H, N, i, j)
    t = _inv_unit_lower(a) if t is None else _solved(a, t)
    ec = jnp.exp(c)[..., None]
    beta = beta[..., None]
    u = rows(_mm("bhnij,bhnjd->bhnid", t.astype(dtype), stored(beta * v)))
    w = rows(_mm("bhnij,bhnjd->bhnid", t.astype(dtype), stored(beta * ec * k)))
    qe, kd = rows(stored(q * ec)), rows(stored(k * jnp.exp(last - c)[..., None]))
    m = rows(_mm("bhnid,bhnjd->bhnij", qh, kh) * decay)
    return (u, w, qe, kd, m, per_head(jnp.exp(last))[..., 0]), t


# -- over the chunks: the scan schedule -----------------------------------------
def _split(a):
    """(B, H, L, d) -> (N, B, H, C, d): the chunks in front."""
    return jnp.moveaxis(a.reshape(a.shape[:2] + (-1, _CHUNK, a.shape[-1])), 2, 0)


def _chunk_first(parts):
    """The pass's arrays with the chunks in front."""
    *rows, gamma = parts
    return (*(_split(a) for a in rows), jnp.moveaxis(gamma, 2, 0))


def _rows_again(a):
    """(N, B, H, C, d) -> (B, H, L, d)."""
    n, b, h, c, d = a.shape
    return jnp.moveaxis(a, 0, 2).reshape(b, h, n * c, d)


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
        return gamma[..., None, None] * s + _mm("bhck,bhcv->bhkv", kd, vp), (o, s)

    _, b, h, _, dv = u.shape
    _, (o, states) = lax.scan(body, jnp.zeros((b, h, w.shape[-1], dv), jnp.float32),
                              (u, w, qe, kd, m, gamma))
    return _rows_again(o), jnp.moveaxis(states, 0, 2)


def _scan_bwd(parts, states, do):
    """The transposes of the pass's three lines, last chunk first: the
    cotangents of ``parts``, in their dtypes."""
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
        d_gamma = jnp.sum(s * ds, axis=(-2, -1))
        d_w = -_mm("bhcv,bhkv->bhck", dvpb, sb)
        ds = (gamma[..., None, None] * ds + _mm("bhck,bhcv->bhkv", qe, do)
              - _mm("bhck,bhcv->bhkv", w, dvpb))
        return ds, (dvp, d_w, d_qe, d_kd, d_m, d_gamma)

    _, (*d, d_gamma) = lax.scan(
        body, jnp.zeros_like(states[:, :, 0]),
        (u, w, qe, kd, m, gamma, jnp.moveaxis(states, 2, 0), do), reverse=True)
    return (*(_rows_again(a).astype(dtype) for a in d), jnp.moveaxis(d_gamma, 0, 2))


# -- over the chunks: the kernels -----------------------------------------------
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _dot(a, b, dims=None):
    # narrow operands multiply exactly in one pass; float32 ones whole
    precision = lax.Precision.DEFAULT if a.dtype.itemsize < 4 else _HI
    if dims is None:
        return jnp.dot(a, b, precision=precision, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _fwd_kernel(u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref, o_ref, states_ref, s_ref,
                *, chunks):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dtype = u_ref.dtype
    for j in range(chunks):
        rows = pl.ds(j * _CHUNK, _CHUNK)
        s = s_ref[...]
        states_ref[0, 0, j] = s
        sb = s.astype(dtype)
        vp = (u_ref[0, 0, rows, :].astype(jnp.float32) - _dot(w_ref[0, 0, rows, :], sb)).astype(dtype)
        o = _dot(qe_ref[0, 0, rows, :], sb) + _dot(m_ref[0, 0, rows, :], vp)
        o_ref[0, 0, rows, :] = o.astype(o_ref.dtype)
        s_ref[...] = gamma_ref[0, 0, j] * s + _dot(kd_ref[0, 0, rows, :], vp, _TN)


def _bwd_kernel(u_ref, w_ref, qe_ref, kd_ref, m_ref, gamma_ref, states_ref, do_ref,
                du_ref, dw_ref, dqe_ref, dkd_ref, dm_ref, dgamma_ref, ds_ref, *, chunks):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dtype = u_ref.dtype
    for j in reversed(range(chunks)):
        rows = pl.ds(j * _CHUNK, _CHUNK)
        s, ds = states_ref[0, 0, j], ds_ref[...]
        sb, dsb = s.astype(dtype), ds.astype(dtype)
        w, qe, kd = w_ref[0, 0, rows, :], qe_ref[0, 0, rows, :], kd_ref[0, 0, rows, :]
        m, do = m_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        vp = (u_ref[0, 0, rows, :].astype(jnp.float32) - _dot(w, sb)).astype(dtype)
        dvp = _dot(m, do, _TN) + _dot(kd, dsb)
        dvpb = dvp.astype(dtype)
        du_ref[0, 0, rows, :] = dvpb
        dw_ref[0, 0, rows, :] = (-_dot(dvpb, sb, _NT)).astype(dtype)
        dqe_ref[0, 0, rows, :] = _dot(do, sb, _NT).astype(dtype)
        dkd_ref[0, 0, rows, :] = _dot(vp, dsb, _NT).astype(dtype)
        dm_ref[0, 0, rows, :] = _dot(do, vp, _NT).astype(dtype)
        dgamma_ref[0, 0, j] = jnp.sum(s * ds, axis=0, keepdims=True)
        ds_ref[...] = gamma_ref[0, 0, j] * ds + _dot(qe, do, _TN) - _dot(w, dvpb, _TN)


def _step_rows(length: int) -> int:
    """Rows a grid step holds: `_STEP_CHUNKS` chunks, or the sequence."""
    return min(_STEP_CHUNKS * _CHUNK, length)


def _specs(dk, dv, rows, steps, reverse):
    """Block specs on the grid (B, H, steps): a head's rows of a (B, H, L,
    width) array, and the chunks' ``gamma`` and states; ``reverse`` walks
    the steps from the last."""
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    chunks = rows // _CHUNK
    row = lambda d: pl.BlockSpec((1, 1, rows, d), lambda b, h, i: (b, h, at(i), 0))  # noqa: E731
    lane = pl.BlockSpec((1, 1, chunks, 1, dv), lambda b, h, i: (b, h, at(i), 0, 0))
    states = pl.BlockSpec((1, 1, chunks, dk, dv), lambda b, h, i: (b, h, at(i), 0, 0))
    return row, lane, states


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _lanes(gamma, dv):
    """A number a chunk, at every lane of a (1, dv) row: how the kernels
    read a scalar."""
    return jnp.broadcast_to(gamma[..., None, None], gamma.shape + (1, dv))


# Jitted, as the flash kernels' callers are: the layers of a model, which
# call them alike, hold one trace and one lowering, not one each.
@functools.partial(jax.jit, static_argnums=(1,))
def _pallas_fwd(parts, interpret):
    u, w, qe, kd, m, gamma = parts
    b, h, length, dv = u.shape
    dk = w.shape[-1]
    rows = _step_rows(length)
    steps, n = length // rows, length // _CHUNK
    row, lane, states = _specs(dk, dv, rows, steps, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=rows // _CHUNK),
        out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((b, h, n, dk, dv), jnp.float32)),
        grid=(b, h, steps),
        in_specs=[row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane],
        out_specs=(row(dv), states),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_gated_delta_fwd",
    )(u, w, qe, kd, m, _lanes(gamma, dv))


@functools.partial(jax.jit, static_argnums=(3,))
def _pallas_bwd(parts, states, do, interpret):
    u, w, qe, kd, m, gamma = parts
    b, h, length, dv = u.shape
    dk = w.shape[-1]
    rows = _step_rows(length)
    steps = length // rows
    row, lane, states_spec = _specs(dk, dv, rows, steps, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    *d, d_gamma = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=rows // _CHUNK),
        out_shape=(like(u), like(w), like(qe), like(kd), like(m),
                   jax.ShapeDtypeStruct(gamma.shape + (1, dv), jnp.float32)),
        grid=(b, h, steps),
        in_specs=[row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane, states_spec, row(dv)],
        out_specs=(row(dv), row(dk), row(dk), row(dk), row(_CHUNK), lane),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="tpuframe_gated_delta_bwd",
    )(u, w, qe, kd, m, _lanes(gamma, dv), states, do.astype(u.dtype))
    return (*d, jnp.sum(d_gamma, axis=(-2, -1)))


# -- inside a chunk: the kernels ------------------------------------------------
#: chunks a grid step of the chunk-local kernels holds at most (a loop in the body)
_LOCAL_CHUNKS = 4


def _solve(a, row, col):
    """`_inv_unit_lower` of one (C, C) array inside a kernel: the same seven
    levels with the same float32 products (`_dot` multiplies float32
    operands whole, at ``HIGHEST``), less the work that is known to
    be nothing.  At the first level ``T`` is ``I``, so ``T - T a T`` is ``I -
    a`` without a product; from blocks of 8 rows on (a float32 tile) a level
    changes the rows of its blocks' lower halves alone, and those rows alone
    are multiplied (priced on the chip beside whole-array products at every
    level and beside products of the level's own block side: PERF.md
    section 6, PR 44).  ``row``, ``col``: the (C, C) iotas."""
    def quarters(level):
        # ``a`` in the lower left quarters of the diagonal blocks of side 2 << level
        inside = ((((row ^ col) >> (level + 1)) == 0)
                  & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0))
        return jnp.where(inside, a, 0.0)

    t = (row == col).astype(jnp.float32) - quarters(0)
    for level in range(1, _CHUNK.bit_length() - 1):
        s, a_s = 1 << level, quarters(level)
        if s < 8:
            t = t - _dot(_dot(t, a_s), t)
            continue
        lower = range(s, _CHUNK, 2 * s)
        low = jnp.concatenate([t[r:r + s] for r in lower], axis=0)
        low = low - _dot(_dot(low, a_s), t)
        t = jnp.concatenate([piece for i, r in enumerate(lower)
                             for piece in (t[r - s:r], low[i * s:(i + 1) * s])], axis=0)
    return t


def _col(row, eye):
    """A number a position along the lanes, (1, C), made down the rows, (C, 1)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """(C, 1) -> (1, C)."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _iotas():
    return (lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 0),
            lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 1))


def _chunk_numbers(numbers_ref, j, h, group, row, col):
    """Chunk ``j``'s numbers of the key head's value head ``h``: ``c`` and
    ``beta`` down the rows (C, 1), made from their copies along the lanes,
    the decays ``D`` (C, C), ``exp(c)`` and ``exp(c_C - c)`` (C, 1)."""
    eye = row == col
    c_row = numbers_ref[0, 0, j, pl.ds(h, 1), :]
    c = _col(c_row, eye)
    beta = _col(numbers_ref[0, 0, j, pl.ds(group + h, 1), :], eye)
    decay = jnp.exp(jnp.where(row >= col, c - c_row, -jnp.inf))
    last = jnp.sum(jnp.where(col[:1] == _CHUNK - 1, c_row, 0.0), axis=1, keepdims=True)
    return c, beta, decay, jnp.exp(c), jnp.exp(last - c)


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, numbers_ref, *refs, chunks, group, solve):
    """`_prepare` for ``chunks`` chunks of a key head and its ``group`` value
    heads, a chunk's (C, C) arrays in VMEM from the decays to ``U`` and
    ``W``.  ``solve`` False is the entry that is handed ``T``."""
    if solve:
        u_ref, w_ref, qe_ref, kd_ref, m_ref, t_ref = refs
    else:
        t_ref, u_ref, w_ref, qe_ref, kd_ref, m_ref = refs
    dtype = v_ref.dtype
    dv = v_ref.shape[-1] // group
    row, col = _iotas()

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * _CHUNK, _CHUNK), _CHUNK)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)  # once a key head
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        for h in range(group):
            c, beta, decay, ec, el = _chunk_numbers(numbers_ref, j, h, group, row, col)
            if solve:
                t = _solve(jnp.where(row > col, beta * kk * decay, 0.0), row, col)
                t_ref[0, h, j] = t
            else:
                t = t_ref[0, h, j]
            tb = t.astype(dtype)
            vf = v_ref[0, rows, h * dv:(h + 1) * dv].astype(jnp.float32)
            u_ref[0, h, rows, :] = _dot(tb, (beta * vf).astype(dtype)).astype(dtype)
            w_ref[0, h, rows, :] = _dot(tb, (beta * ec * kf).astype(dtype)).astype(dtype)
            qe_ref[0, h, rows, :] = (qf * ec).astype(dtype)
            kd_ref[0, h, rows, :] = (kf * el).astype(dtype)
            m_ref[0, h, rows, :] = (qk * decay).astype(dtype)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, numbers_ref, t_ref, du_ref, dw_ref, dqe_ref, dkd_ref,
                      dm_ref, dq_ref, dk_ref, dv_ref, dnumbers_ref, *, chunks, group):
    """The transpose of `_chunk_fwd_kernel`, a chunk at a time: the
    cotangents of ``q`` and ``k`` summed over the key head's value heads, of
    ``v``, and of ``c`` and ``beta`` along the lanes as they came."""
    dtype = v_ref.dtype
    dv = v_ref.shape[-1] // group
    row, col = _iotas()
    eye = row == col
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)  # noqa: E731

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * _CHUNK, _CHUNK), _CHUNK)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        d_kk = d_qk = jnp.zeros_like(kk)
        dq = dk = jnp.zeros_like(kf)
        for h in range(group):
            c, beta, decay, ec, el = _chunk_numbers(numbers_ref, j, h, group, row, col)
            t = t_ref[0, h, j]
            tb = t.astype(dtype)
            lanes = slice(h * dv, (h + 1) * dv)
            vf = v_ref[0, rows, lanes].astype(jnp.float32)
            bec = beta * ec
            du, dw = du_ref[0, h, rows, :], dw_ref[0, h, rows, :]
            # U = T (B V), W = T (B K e^c): into T, and through the inverse into A
            d_t = (_dot(du, (beta * vf).astype(dtype), _NT)
                   + _dot(dw, (bec * kf).astype(dtype), _NT))
            d_a = jnp.where(row > col, -_dot(_dot(t, d_t, _TN), t, _NT), 0.0)
            # A = beta K K^T D under the diagonal, M = Q K^T D on and under it
            x, y = d_a * decay, dm_ref[0, h, rows, :].astype(jnp.float32) * decay
            p = x * kk
            d_kk, d_qk = d_kk + beta * x, d_qk + y
            e = beta * p + y * qk  # the cotangent of c_i - c_j
            d_c = rowsum(e) - _col(jnp.sum(e, axis=0, keepdims=True), eye)
            d_bv, d_bke = _dot(tb, du, _TN), _dot(tb, dw, _TN)
            dv_ref[0, rows, lanes] = (beta * d_bv).astype(dtype)
            r = rowsum(d_bke * kf)
            d_beta = rowsum(p) + rowsum(d_bv * vf) + r * ec
            d_qe = dqe_ref[0, h, rows, :].astype(jnp.float32)
            d_kd = dkd_ref[0, h, rows, :].astype(jnp.float32)
            dq = dq + d_qe * ec
            dk = dk + bec * d_bke + d_kd * el
            z = rowsum(d_kd * kf) * el  # Kd = K exp(c_C - c): c_C is the last row's c
            d_c = (d_c + (r * beta + rowsum(d_qe * qf)) * ec - z
                   + jnp.where(row[:, :1] == _CHUNK - 1, jnp.sum(z, axis=0, keepdims=True), 0.0))
            dnumbers_ref[0, 0, j, pl.ds(h, 1), :] = _row(d_c, eye)
            dnumbers_ref[0, 0, j, pl.ds(group + h, 1), :] = _row(d_beta, eye)
        d_kk, d_qk = d_kk.astype(dtype), d_qk.astype(dtype)
        dq_ref[0, rows, :] = (dq + _dot(d_qk, k)).astype(dtype)
        dk_ref[0, rows, :] = (dk + _dot(d_qk, q, _TN) + _dot(d_kk, k)
                              + _dot(d_kk, k, _TN)).astype(dtype)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _numbers(g, beta, hk):
    """The per-position numbers as the chunk-local kernels read them, a
    number a position along the lanes: (B, L, H) -> (B, Hk, N, 2 G, C)
    float32, a chunk's ``c`` (the sum of ``g`` from its first position) a
    value head of the key head, then its ``beta`` a value head; and
    ``gamma`` (B, H, N).  XLA's, over (B, L, H) numbers, and differentiated
    by autodiff (the reverse prefix sum from ``dc`` to ``dg`` is in it)."""
    b, length, h = g.shape
    group, n = h // hk, length // _CHUNK
    lanes = lambda a: jnp.transpose(  # noqa: E731
        a.astype(jnp.float32).reshape(b, n, _CHUNK, hk, group), (0, 3, 1, 4, 2))
    c = jnp.cumsum(lanes(g), axis=-1)
    gamma = jnp.swapaxes(jnp.exp(c[..., -1]), 2, 3).reshape(b, h, n)
    return jnp.concatenate([c, lanes(beta)], axis=3), gamma


def _chunk_call(kernel, name, q, v, interpret):
    """-> ``pl.pallas_call`` with one chunk-local kernel on the grid (rows,
    key heads, blocks of chunks), every axis parallel, and its block specs:
    ``key`` and ``value`` a key head's lanes of the model's rows (B, L,
    heads * width), ``numbers``, ``head(width)`` the key head's value heads of
    a (B, H, L, width) array and ``solved`` of ``T`` (B, H, N, C, C)."""
    b, length, hk, dk = q.shape
    h, dv = v.shape[2:]
    group, n = h // hk, length // _CHUNK
    chunks = math.gcd(n, _LOCAL_CHUNKS)
    rows = chunks * _CHUNK
    model = lambda width: pl.BlockSpec((1, rows, width), lambda b, h, i: (b, i, h))  # noqa: E731
    heads = lambda *block: pl.BlockSpec(  # noqa: E731
        (1, group) + block, lambda b, h, i: (b, h, i) + (0,) * (len(block) - 1))
    specs = SimpleNamespace(
        key=model(dk), value=model(group * dv),
        numbers=pl.BlockSpec((1, 1, chunks, 2 * group, _CHUNK), lambda b, h, i: (b, h, i, 0, 0)),
        head=lambda width: heads(rows, width), solved=heads(chunks, _CHUNK, _CHUNK))
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, chunks=chunks, group=group),
        grid=(b, hk, n // chunks),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name=name)
    return call, specs


def _model_rows(*arrays):
    """(B, L, heads, width) -> (B, L, heads * width): no copy."""
    return tuple(a.reshape(a.shape[:2] + (-1,)) for a in arrays)


def _chunk_parts(q, k, v, g, beta, t, interpret):
    """`_prepare` as a kernel: with ``t`` None the one that solves (-> the
    parts and ``T``), else the one that is handed ``T`` (-> the parts)."""
    b, length, hk, dk = q.shape
    h, dv = v.shape[2:]
    call, s = _chunk_call(functools.partial(_chunk_fwd_kernel, solve=t is None),
                          "tpuframe_delta_chunk_" + ("fwd" if t is None else "again"),
                          q, v, interpret)
    numbers, gamma = _numbers(g, beta, hk)
    row = lambda width: jax.ShapeDtypeStruct((b, h, length, width), v.dtype)  # noqa: E731
    parts = (row(dv), row(dk), row(dk), row(dk), row(_CHUNK))
    part_specs = (s.head(dv), s.head(dk), s.head(dk), s.head(dk), s.head(_CHUNK))
    solved = jax.ShapeDtypeStruct((b, h, length // _CHUNK, _CHUNK, _CHUNK), jnp.float32)
    own = [s.key, s.key, s.value, s.numbers]
    if t is None:
        *parts, t = call(out_shape=parts + (solved,), in_specs=own,
                         out_specs=part_specs + (s.solved,))(*_model_rows(q, k, v), numbers)
        return (*parts, gamma), t
    parts = call(out_shape=parts, in_specs=own + [s.solved],
                 out_specs=part_specs)(*_model_rows(q, k, v), numbers, t)
    return (*parts, gamma)


# Jitted like the pass's callers: one trace and one lowering for a model's layers.
@functools.partial(jax.jit, static_argnums=(5,))
def _pallas_chunk_fwd(q, k, v, g, beta, interpret):
    return _chunk_parts(q, k, v, g, beta, None, interpret)


@functools.partial(jax.jit, static_argnums=(6,))
def _pallas_chunk_again(q, k, v, g, beta, t, interpret):
    return _chunk_parts(q, k, v, g, beta, t, interpret)


@functools.partial(jax.jit, static_argnums=(7,))
def _pallas_chunk_bwd(q, k, v, g, beta, t, d_parts, interpret):
    """The cotangents of the op's five inputs from those of the parts."""
    *d_rows, d_gamma = d_parts
    call, s = _chunk_call(_chunk_bwd_kernel, "tpuframe_delta_chunk_bwd", q, v, interpret)
    (numbers, _), transpose = jax.vjp(lambda g, beta: _numbers(g, beta, q.shape[2]), g, beta)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    rows = _model_rows(q, k, v)
    dq, dk, dv, d_numbers = call(
        out_shape=tuple(like(a) for a in (*rows, numbers)),
        in_specs=[s.key, s.key, s.value, s.numbers, s.solved,
                  *(s.head(a.shape[-1]) for a in d_rows)],
        out_specs=(s.key, s.key, s.value, s.numbers))(*rows, numbers, t, *d_rows)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            *transpose((d_numbers, d_gamma)))


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
        parts, t = _pallas_chunk_fwd(q, k, v, g, beta, interpret)
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
    # a kernel of its own computes them again: nothing for XLA to merge, and
    # no barrier (the step's temporaries are 0.05 GiB lower without it)
    do = jnp.swapaxes(do, 1, 2)
    parts = _pallas_chunk_again(*inputs, t, interpret)
    d_parts = _pallas_bwd(parts, states, do, interpret)
    return _pallas_chunk_bwd(*inputs, t, d_parts, interpret)


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
    if (q.shape != k.shape or q.shape[:2] != (b, length) or h % q.shape[2]
            or g.shape != (b, length, h) or beta.shape != g.shape):
        raise ValueError(
            f"q {q.shape}, k {k.shape} are not (B, L, Hk, dk), v {v.shape} (B, L, H, dv) "
            f"with Hk dividing H, g {g.shape}, beta {beta.shape} (B, L, H)")


def chunks_walked(batch: int, length: int, heads: int) -> int:
    """Chunk steps one call of the op makes, forward and backward together:
    what the counter ``deltanet/chunks`` adds a call."""
    return 2 * batch * heads * (pad_to(length, _CHUNK) // _CHUNK)


def gated_delta_chunked(q, k, v, g, beta):
    """The chunked schedule with the pass over the chunks as a
    ``lax.scan``: shapes and results as :func:`gated_delta_reference`."""
    _check(q, k, v, g, beta)
    return _padded(q, k, v, g, beta, interpret=None)


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                interpret: bool | None = None, *, mesh=None,
                batch_axes: tuple | None = None) -> jax.Array:
    """The gated delta rule of ``q``, ``k`` (B, L, Hk, dk), ``v`` (B, L, H, dv),
    ``g`` and ``beta`` (B, L, H) -> (B, L, H, dv).  Differentiable in all five.

    ``interpret``: None = auto (the kernels on a TPU, the scan schedule
    elsewhere, by `resolve_interpret`); the op's own shape rule asks for
    heads of whole lanes (``dk`` and ``dv`` multiples of 128).  On a ``mesh``
    whose batch axes divide the rows the kernels run per shard under
    ``shard_map`` (rows are independent).
    """
    _check(q, k, v, g, beta)
    if interpret is None and (q.shape[-1] % _LANES or v.shape[-1] % _LANES):
        return _padded(q, k, v, g, beta, interpret=None)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, v.shape[0])
    interpret = resolve_interpret(
        interpret, shardable, op="gated_delta",
        shape_class=shape_class(l=v.shape[1], h=v.shape[2], c=_CHUNK))
    run = functools.partial(_padded, interpret=interpret)
    if interpret is not None and shardable and n_shards > 1:
        row, head = P(axes, None, None, None), P(axes, None, None)
        return shard_map(run, mesh=mesh, in_specs=(row, row, row, head, head),
                         out_specs=row, check_vma=False)(q, k, v, g, beta)
    return run(q, k, v, g, beta)
