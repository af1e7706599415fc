"""The learned index of sparse attention: which keys a query may see (Pallas).

A DeepSeek-Sparse-Attention layer lets a query attend to the ``topk`` keys
a second, small attention ranks highest.  With index queries ``qI`` (B, L,
Hi, Di), one index key head ``kI`` (B, L, Di) and head weights ``w`` (B, L,
Hi), the index score of query ``t`` and key ``s <= t`` is::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

(float32 products' sums, the heads added in their order from +0.0), and the
choice ``S_t`` is the ``topk`` keys ``s <= t`` with the largest ``I[t, s]``:
every ``s <= t`` where ``t + 1 <= topk``, exactly ``topk`` otherwise, ties to
the earlier key (`jax.lax.top_k`'s order).  The op returns the choice as
what `ring_attention.SelectedKeysMask` reads, one byte a (query, key) pair
shared by all heads: ``chosen`` (B, L, L) int8, 1 where ``s`` is in ``S_t``;
and each query's count of chosen keys (B, L) float32.  It has no gradient:
the choice is discrete, and a caller stops gradients at its inputs.

The kernel ``tpuframe_index_topk`` holds a tile of queries' scores over
every key not after them in VMEM and never sorts: the ``topk``-th largest
score a row is found exactly by bisection on the scores' bits (float32
compares as a signed integer after one flip: 32 counting passes over the
tile), and the ties at that value are cut at the key index that leaves
exactly ``topk`` (one more bisection, over positions, run only for a tile
that holds a row with such ties).  Tiles whose last query lies under
``topk`` choose every key not after the query and compute no score.
:func:`select_keys_reference` is the oracle (`lax.top_k` over a block of
queries' scores at a time), and what a CPU, ``init`` and every call the
engage rule turns away run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpuframe.ops.blockwise_attention import _col_to_row
from tpuframe.ops.dispatch import batch_sharding_info, pad_to, resolve_interpret
from tpuframe.ops.registry import shape_class

__all__ = ["index_scores_reference", "select_keys", "select_keys_reference"]

_LANES = 128
#: queries a grid step ranks, keys a chunk of their scores holds: the
#: float32 scores of 256 queries over 8192 keys are 8 MiB of VMEM
_TILE_QUERIES = 256
_CHUNK_KEYS = 512
#: the oracle's block of queries: (block, Hi, L) float32 products at a time
_ORACLE_BLOCK = 512
_VMEM_BYTES = 64 << 20
_INT_MIN = -(2 ** 31)


def _check(qi, ki, w):
    if (qi.ndim != 4 or ki.shape != (qi.shape[0], qi.shape[1], qi.shape[3])
            or w.shape != qi.shape[:3]):
        raise ValueError(f"index queries {qi.shape}, keys {ki.shape} and weights {w.shape} are "
                         "not (B, L, Hi, Di), (B, L, Di) and (B, L, Hi)")


def _pad_rows(a, l_pad):
    """(B, L, ...) -> (B, l_pad, ...), zeros behind the row."""
    return jnp.pad(a, [(0, 0), (0, l_pad - a.shape[1])] + [(0, 0)] * (a.ndim - 2))


def index_scores_reference(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """``I`` (B, Lq, Lk) float32 for index queries (B, Lq, Hi, Di), keys
    (B, Lk, Di) and weights (B, Lq, Hi): no mask, the heads added in their
    order from +0.0 (so a score is never -0.0)."""
    acc = jnp.zeros((qi.shape[0], qi.shape[1], ki.shape[1]), jnp.float32)
    for j in range(qi.shape[2]):
        s = jnp.einsum("bqd,bkd->bqk", qi[:, :, j], ki, preferred_element_type=jnp.float32)
        acc = acc + w[:, :, j, None].astype(jnp.float32) * jnp.maximum(s, 0.0)
    return acc


def select_keys_reference(qi: jax.Array, ki: jax.Array, w: jax.Array, topk: int):
    """The oracle -> (``chosen`` (B, L, L) int8, ``counts`` (B, L) float32):
    `jax.lax.top_k` over the scores of a block of queries at a time, keys
    after the query at -inf and dropped again where a row has fewer than
    ``topk`` keys."""
    _check(qi, ki, w)
    b, l = qi.shape[:2]
    block = min(_ORACLE_BLOCK, l)
    l_pad = pad_to(l, block)
    keys = jnp.arange(l)[None, None, :]
    picks = min(topk, l)

    def rows(args):
        q_blk, w_blk, first = args                     # (B, block, Hi, Di), (B, block, Hi)
        seen = keys <= (first + jnp.arange(block))[None, :, None]
        scores = jnp.where(seen, index_scores_reference(q_blk, ki, w_blk), -jnp.inf)
        _, at = lax.top_k(scores, picks)               # (B, block, picks)
        hit = jnp.zeros(scores.shape, jnp.int8).at[
            jnp.arange(b)[:, None, None], jnp.arange(block)[None, :, None], at].set(1)
        return jnp.where(seen, hit, 0).astype(jnp.int8)

    blocks = lambda a: jnp.moveaxis(  # noqa: E731
        _pad_rows(a, l_pad).reshape(b, l_pad // block, block, *a.shape[2:]), 1, 0)
    chosen = lax.map(rows, (blocks(qi), blocks(w), jnp.arange(l_pad // block) * block))
    chosen = jnp.moveaxis(chosen, 0, 1).reshape(b, l_pad, l)[:, :l]
    return chosen, jnp.sum(chosen, axis=-1, dtype=jnp.float32)


# -- the kernel ------------------------------------------------------------------
def _sortable(x):
    """float32 -> int32 whose signed order is the floats' (no -0.0 comes in)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ (lax.shift_right_arithmetic(bits, jnp.full_like(bits, 31)) & 0x7FFFFFFF)


def _count(flags):
    """(rows, keys) bool -> (rows, 1) float32 (a row holds at most 2^24 keys)."""
    return jnp.sum(jnp.where(flags, 1.0, 0.0), axis=1, keepdims=True)


def _index_kernel(q_ref, k_ref, w_ref, chosen_ref, count_ref, keys_ref, *,
                  topk, length, tq, tk, heads, di):
    """One tile of ``tq`` queries against every key not after them, in chunks
    of ``tk`` keys: scores as sortable integers into ``keys_ref`` (chunks,
    tq, tk), the two bisections, the choice written a chunk at a time."""
    n_chunks = keys_ref.shape[0]
    q_lo = pl.program_id(1) * tq
    rows = q_lo + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, tk), 1)
    # chunks that hold a key not after the tile's last query
    live = jnp.minimum((q_lo + tq + tk - 1) // tk, n_chunks)
    side = _LANES // di if di < _LANES else 1     # index heads side by side in 128 lanes
    of_head = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // di

    def seen(c):
        at = c * tk + cols
        return (at <= rows) & (at < length)

    def write(choice):
        """``choice(c)`` (tq, tk) bool a live chunk -> the block, and the count
        of what it chose a row (counted, not reckoned: the counters' proof)."""
        for c in range(n_chunks):
            @pl.when(c < live)
            def _(c=c):
                chosen_ref[:, c * tk:(c + 1) * tk] = choice(c).astype(jnp.int8)

            @pl.when(c >= live)
            def _(c=c):
                chosen_ref[:, c * tk:(c + 1) * tk] = jnp.zeros((tq, tk), jnp.int8)
        total = lax.fori_loop(0, live, lambda c, n: n + _count(choice(c)),
                              jnp.zeros((tq, 1), jnp.float32))
        count_ref[...] = _col_to_row(total)

    @pl.when(q_lo + tq <= topk)
    def _():
        # every row of the tile chooses every key not after it: no score
        write(seen)

    @pl.when(q_lo + tq > topk)
    def _():
        def score(c, carry):
            k = k_ref[pl.ds(pl.multiple_of(c * tk, tk), tk), :]       # (tk, 128)
            acc = jnp.zeros((tq, tk), jnp.float32)
            for g in range(heads // side):
                q = q_ref[:, g * side * di:(g + 1) * side * di]        # whole lanes
                for r in range(side):
                    one = q if side == 1 else jnp.where(of_head == r, q, jnp.zeros_like(q))
                    s = lax.dot_general(
                        one, k, (((1,), (1,)), ((), ())),
                        precision=lax.Precision.DEFAULT if q.dtype.itemsize < 4 else None,
                        preferred_element_type=jnp.float32)
                    j = g * side + r
                    acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
            keys_ref[c] = jnp.where(seen(c), _sortable(acc), _INT_MIN)
            return carry

        lax.fori_loop(0, live, score, 0)

        def count(flags_of):
            """(tq, 1) float32: ``flags_of(chunk of keys, chunk index)`` counted
            over the live chunks."""
            return lax.fori_loop(
                0, live, lambda c, n: n + _count(flags_of(keys_ref[c], c)),
                jnp.zeros((tq, 1), jnp.float32))

        # the topk-th largest key a row: the largest T with topk keys >= T,
        # built a bit at a time from the sign down (a row with fewer keys
        # than topk keeps T at the least integer, under every key it has)
        def bit(i, t):
            cand = t ^ lax.shift_left(jnp.int32(1), 31 - i)
            enough = count(lambda keys, c: keys >= cand) >= topk
            return jnp.where(enough, cand, t)

        t = lax.fori_loop(0, 32, bit, jnp.full((tq, 1), _INT_MIN, jnp.int32))
        above = count(lambda keys, c: keys > t)
        ties = count(lambda keys, c: keys == t)
        need = topk - above                      # ties at T a row still takes
        # keys at T are taken from the earliest on: those before position
        # ``cut``, the largest position with no more than ``need`` ties before it
        no_cut = jnp.full((tq, 1), n_chunks * tk, jnp.int32)

        def cut_of(_):
            def bit(i, p):
                cand = p | lax.shift_left(jnp.int32(1), (n_chunks * tk).bit_length() - 1 - i)
                before = count(lambda keys, c: (keys == t) & (c * tk + cols < cand))
                return jnp.where(before <= need, cand, p)

            return lax.fori_loop(0, (n_chunks * tk).bit_length(), bit,
                                 jnp.zeros((tq, 1), jnp.int32))

        # some row has more ties than it takes (a row with fewer keys than topk
        # takes them all: its T is the padding's)
        excess = jnp.max(jnp.where(t > _INT_MIN, ties - need, 0.0)) > 0.0
        cut = lax.cond(excess, cut_of, lambda _: no_cut, 0)

        def choice(c):
            keys = keys_ref[c]
            return seen(c) & ((keys > t) | ((keys == t) & (c * tk + cols < cut)))

        write(choice)


def _tiles(length):
    """(padded length, queries a tile, keys a chunk) for ``length`` positions."""
    l_pad = pad_to(length, _LANES if length <= _TILE_QUERIES else _CHUNK_KEYS)
    return l_pad, min(_TILE_QUERIES, l_pad), min(_CHUNK_KEYS, l_pad)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select(qi, ki, w, *, topk, interpret):
    """Jitted for itself: a model's layers share one trace and one lowering."""
    b, l, heads, di = qi.shape
    l_pad, tq, tk = _tiles(l)
    side = max(_LANES // di, 1)
    pad = functools.partial(_pad_rows, l_pad=l_pad)
    # the one key head, side by side as often as index heads share 128 lanes
    k2 = jnp.concatenate([pad(ki)] * side, axis=-1)
    chosen, counts = pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, length=l, tq=tq, tk=tk,
                          heads=heads, di=di),
        grid=(b, l_pad // tq),
        in_specs=[
            pl.BlockSpec((None, tq, heads * di), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((None, l_pad, side * di), lambda b_, i: (b_, 0, 0)),
            pl.BlockSpec((None, tq, heads), lambda b_, i: (b_, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, tq, l_pad), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((None, 1, tq), lambda b_, i: (b_, 0, i)),
        ),
        out_shape=(jax.ShapeDtypeStruct((b, l_pad, l_pad), jnp.int8),
                   jax.ShapeDtypeStruct((b, 1, l_pad), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((l_pad // tk, tq, tk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="tpuframe_index_topk",
    )(pad(qi).reshape(b, l_pad, heads * di), k2, pad(w).astype(jnp.float32))
    return chosen[:, :l, :l], counts[:, 0, :l]


def select_keys(qi: jax.Array, ki: jax.Array, w: jax.Array, topk: int, *,
                interpret: bool | None = None, mesh=None,
                batch_axes: tuple | None = None):
    """The choice of ``topk`` keys a query -> (``chosen`` (B, L, L) int8,
    ``counts`` (B, L) float32), from index queries ``qi`` (B, L, Hi, Di),
    the index key head ``ki`` (B, L, Di) and head weights ``w`` (B, L, Hi).
    Exact, ties to the earlier key; no gradient.

    ``interpret``: None = auto (the kernel on a TPU, the oracle elsewhere, by
    `resolve_interpret`); the op's own shape rule asks for index heads that
    fill whole lanes (``Di`` 128 wide, or 64 or 32 with the heads in whole
    blocks of 128 lanes) and a tile's scores within VMEM.  On a ``mesh`` whose
    batch axes divide the rows the kernel runs per shard under ``shard_map``."""
    _check(qi, ki, w)
    if topk < 1:
        raise ValueError(f"topk {topk}: a query sees at least one key")
    b, l, heads, di = qi.shape
    l_pad, tq, _ = _tiles(l)
    lanes = di == _LANES or (di in (32, 64) and (heads * di) % _LANES == 0)
    if interpret is None and (not lanes or 4 * tq * l_pad > _VMEM_BYTES // 2):
        return select_keys_reference(qi, ki, w, topk)
    axes, n_shards, shardable = batch_sharding_info(mesh, batch_axes, b)
    interpret = resolve_interpret(
        interpret, shardable, op="sparse_index", shape_class=shape_class(l=l, h=heads, d=di))
    if interpret is None:
        return select_keys_reference(qi, ki, w, topk)
    run = functools.partial(_select, topk=topk, interpret=interpret)
    if shardable and n_shards > 1:
        return shard_map(
            run, mesh=mesh,
            in_specs=(P(axes, None, None, None), P(axes, None, None), P(axes, None, None)),
            out_specs=(P(axes, None, None), P(axes, None)), check_vma=False)(qi, ki, w)
    return run(qi, ki, w)
