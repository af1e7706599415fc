"""Un-sort of the expert layer's slots: each token's routed rows summed.

``unsort(rows, tok, sizes, n)`` is ``out[t] = sum of rows[a] over the live
slots a with tok[a] == t``: ``rows`` (cap, d) is a window of the slots the
no-drop expert layer sorted by expert, ``tok`` (cap,) names each slot's
token, ``sizes`` (G,) is how many of the window's slots each held expert
has, in order from slot 0, and a slot is live when it lies under
``sum(sizes)``.  ``out`` is (n, d) in ``rows``' dtype, accumulated in
float32 and rounded once; a token with no live slot reads zero, and what
the slots past the groups hold is never read into a sum (the live rows are
taken to be finite: the kernel multiplies a window's other live rows by
zero).  Inside a group
the slots come by token, ascending (the layer's stable sort over pairs
numbered ``token * k + choice`` makes them so): the rows one tile of
tokens needs from one expert are then one contiguous run of slots.

XLA's form (``models/moe.py::_sum_choices_impl``) gathers a row for every
(token, choice) pair, routed here or not, into a (k, n, d) array and reduces
it.  The kernel ``tpuframe_unsort`` reads the routed rows alone and writes
each token's row once:

- a plan in scalar memory, sums and compares over a (tiles, G) table, gives
  every (tile of ``_TOKENS`` tokens, expert) its run of slots ``[lo, hi)``;
- a grid step fetches, for each held expert, one window of ``W`` slots from
  the run's start (rounded down to the dtype's sublane tile) out of ``rows``
  in HBM into one (G * W, d) buffer, the next tile's windows in flight
  while this tile multiplies;
- ``onehot[a, r] = slot a is in its run and tok[a] == tile's first + r``
  and ``out = onehot^T @ buffer`` on the MXU: exact (a row times one), the
  sum over a token's choices made in the float32 accumulator.  A run longer
  than its window takes further rounds of windows, added in float32.

Wherever the kernel does not run (a CPU, a process of several devices, a
manual region, a shape the rule below refuses) :func:`unsort` returns what
its caller's ``otherwise`` computes (the expert layer: XLA's form as it
stood).  :func:`unsort_reference`, a scatter-add, is the tests' oracle.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuframe.ops.grouped_matmul import _TN, _engage
from tpuframe.ops.registry import shape_class

__all__ = ["unsort", "unsort_reference", "unsort_window"]

#: tokens a grid step writes: the rows of its product on the MXU (and the
#: most a bfloat16 holds exactly, which carries a slot's place in its tile)
_TOKENS = 256
_LANES = 128
#: the windows' buffers (two tiles' and a further round's) may take this much
_BUFFER_BYTES = 24 << 20
_VMEM_BYTES = 100 << 20


def unsort_reference(rows: jax.Array, tok: jax.Array, sizes: jax.Array, n: int) -> jax.Array:
    """jnp oracle: a float32 scatter-add of the live rows, rounded once."""
    live = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
    picked = jnp.where(live[:, None], rows.astype(jnp.float32), 0.0)
    out = jnp.zeros((n, rows.shape[1]), jnp.float32).at[jnp.where(live, tok, 0)].add(picked)
    return out.astype(rows.dtype)


def _sublanes(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize


def unsort_window(cap: int, d: int, groups: int, n: int, dtype) -> int | None:
    """Slots of one expert's window a grid step, from the shapes alone, or
    None where the kernel does not take them.  Twice the run a balanced
    router gives a (tile, expert) in buffers it half fills, and the
    sublane tile its start is rounded down by, in whole sublane tiles,
    between 32 and 128: a run that long is one round."""
    tokens = min(_TOKENS, n)
    align = _sublanes(dtype)
    if d % _LANES or n % tokens or tokens % 8 or cap % align or groups < 1:
        return None
    run = cap // (2 * (n // tokens) * groups)
    window = min(max(32, -(-(2 * run + align) // 16) * 16), 128)
    if window > cap or 3 * groups * window * (d + _LANES) * jnp.dtype(dtype).itemsize > _BUFFER_BYTES:
        return None
    return window


def _runs(tok, sizes, n: int, tokens: int):
    """``(lo, hi)``, each (tiles * G,): the slots ``[lo, hi)`` of group ``e``
    whose tokens lie in tile ``i``, at ``i * G + e``.  A live slot's key
    ``group * n + token`` ascends along the slots, so a run's start is the
    count of keys under ``e * n + i * tokens``: compares and a sum over a
    (slots, tiles * G) table, nothing gathered."""
    groups, tiles = sizes.shape[0], n // tokens
    ends = lax.cumsum(sizes)
    slot = lax.iota(jnp.int32, tok.shape[0])
    group = jnp.sum((slot[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    key = jnp.where(slot < ends[-1], group * n + tok, jnp.iinfo(jnp.int32).max)
    edge = (lax.iota(jnp.int32, tiles + 1)[:, None] * tokens
            + lax.iota(jnp.int32, groups)[None, :] * n)
    under = jnp.sum((key[:, None, None] < edge[None]).astype(jnp.int32), axis=0)
    return under[:-1].reshape(-1), under[1:].reshape(-1)


def _kernel(lo_ref, hi_ref, total_ref, rows_ref, place_ref, out_ref, buf, places, sem, inside_ref,
            acc_ref, *, groups, window, align, cap, precision):
    # loops over the groups, not Python's: the body is lowered once a jitted
    # caller, and unrolled it cost a job's first step seconds
    i = pl.program_id(0)
    tokens = out_ref.shape[0]

    def run(tile, e):
        lo, hi = lo_ref[tile * groups + e], hi_ref[tile * groups + e]
        return lo, hi, (lo // align) * align

    def fetched(tile, e, r):
        """First slot of group ``e``'s window in round ``r``: ``window`` on
        from the run's start rounded down, pushed back where the buffer ends."""
        return jnp.minimum(run(tile, e)[2] + r * window, cap - window)

    def copies(tile, r, slot, do):
        """``do`` the copies of the round's windows of ``tile``, a group's
        after a group's."""
        def one(e, _):
            at = pl.ds(pl.multiple_of(fetched(tile, e, r), align), window)
            to = pl.ds(pl.multiple_of(e * window, align), window)
            do(pltpu.make_async_copy(rows_ref.at[at, :], buf.at[slot, to, :], sem.at[slot, 0, e]))
            do(pltpu.make_async_copy(place_ref.at[at, :], places.at[slot, to, :], sem.at[slot, 1, e]))
            return _

        lax.fori_loop(0, groups, one, None)

    start, wait = operator.methodcaller("start"), operator.methodcaller("wait")

    def product(r, slot, clean):
        """(tokens, d) float32: the round's windows, each slot's row added
        to its token's.  ``clean``: no window reaches past the groups, where
        the rows may hold anything (a select then, never a multiply)."""
        row = lax.broadcasted_iota(jnp.int32, (window, 1), 0)

        def mark(e, _):
            lo, hi, floor = run(i, e)
            slot_id = fetched(i, e, r) + row
            # a window the buffer's end pushed back holds slots of an earlier round
            mine = (slot_id >= jnp.maximum(lo, floor + r * window)) & (slot_id < hi)
            inside_ref[pl.ds(pl.multiple_of(e * window, align), window), :] = mine.astype(jnp.int32)
            return _

        lax.fori_loop(0, groups, mark, None)
        inside = inside_ref[...] > 0
        place = places[slot][:, :1].astype(jnp.float32)
        column = lax.broadcasted_iota(jnp.int32, (groups * window, tokens), 1).astype(jnp.float32)
        x = buf[slot]
        onehot = jnp.where(inside & (place == column), 1.0, 0.0).astype(x.dtype)
        if not clean:
            x = jnp.where(inside, x.astype(jnp.float32), 0.0).astype(x.dtype)
        return lax.dot_general(onehot, x, _TN, precision=precision,
                               preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        copies(0, 0, 0, start)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        copies(i + 1, 0, (i + 1) % 2, start)

    copies(i, 0, i % 2, wait)

    def extent(e, most):
        lo, hi, floor = run(i, e)
        return (jnp.maximum(most[0], (hi - floor + window - 1) // window),
                jnp.maximum(most[1], fetched(i, e, 0) + window))

    rounds, reach = lax.fori_loop(0, groups, extent, (jnp.int32(1), jnp.int32(0)))
    clean = reach <= total_ref[0]

    @pl.when(clean)
    def _():
        acc_ref[...] = product(0, i % 2, True)

    @pl.when(~clean)
    def _():
        acc_ref[...] = product(0, i % 2, False)

    def further(r, _):
        copies(i, r, 2, start)
        copies(i, r, 2, wait)
        acc_ref[...] += product(r, 2, False)
        return _

    lax.fori_loop(1, rounds, further, None)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "window", "interpret"))
def _unsort(rows, tok, sizes, *, n, window, interpret):
    cap, d = rows.shape
    groups, tokens = sizes.shape[0], min(_TOKENS, n)
    lo, hi = _runs(tok, sizes, n, tokens)
    # a slot's place in its tile of tokens, a row of lanes a slot so that a
    # window of them is fetched as the rows' is; tied to the rows, so that it
    # is made beside each call: the one array a layer's forward and backward
    # calls would share lives across the step's peak (cap x 256 B a layer)
    rows, at = lax.optimization_barrier((rows, tok % tokens))
    place = jnp.broadcast_to(at.astype(rows.dtype)[:, None], (cap, _LANES))
    k = groups * window
    return pl.pallas_call(
        functools.partial(
            _kernel, groups=groups, window=window, align=_sublanes(rows.dtype), cap=cap,
            precision=(lax.Precision.DEFAULT if rows.dtype.itemsize < 4
                       else lax.Precision.HIGHEST)),
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tokens,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((3, k, d), rows.dtype),
                            pltpu.VMEM((3, k, _LANES), rows.dtype),
                            pltpu.SemaphoreType.DMA((3, 2, groups)),
                            pltpu.VMEM((k, 1), jnp.int32),
                            pltpu.VMEM((tokens, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="tpuframe_unsort",
    )(lo, hi, jnp.sum(sizes).reshape(1), rows, place)


def unsort(rows: jax.Array, tok: jax.Array, sizes: jax.Array, n: int, *,
           interpret: bool | None = None, otherwise=None) -> jax.Array:
    """(cap, d) rows of sorted slots -> (n, d): every token's live rows
    summed (the module's docstring has the contract).

    ``interpret``: None = auto (the kernel on a one-device TPU process).
    ``otherwise()`` computes the result wherever the kernel does not run
    (the expert layer hands XLA's form as it stood); with none, that is an
    error: :func:`unsort_reference` is the tests' oracle and never what a
    program falls back to unasked."""
    if rows.ndim != 2 or tok.shape != rows.shape[:1] or sizes.ndim != 1:
        raise ValueError(f"unsort takes (cap, d) rows, (cap,) tokens and (G,) sizes, got "
                         f"{rows.shape}, {tok.shape} and {sizes.shape}")
    (cap, d), groups = rows.shape, sizes.shape[0]
    window = unsort_window(cap, d, groups, n, rows.dtype)
    if window is None and interpret is not None:
        raise ValueError(f"the un-sort kernel takes no ({cap}, {d}) {rows.dtype} rows of "
                         f"{groups} groups for {n} tokens")
    if window is not None:
        interpret = _engage(interpret, op="unsort",
                            shape_class=shape_class(cap=cap, d=d, g=groups, n=n),
                            engaged_attrs={"tokens": min(_TOKENS, n), "window": window})
    if window is not None and interpret is not None:
        return _unsort(rows, tok.astype(jnp.int32), sizes.astype(jnp.int32), n=n, window=window,
                       interpret=interpret)
    if otherwise is None:
        raise ValueError(f"no un-sort kernel runs here for ({cap}, {d}) {rows.dtype} rows of "
                         f"{groups} groups and {n} tokens, and the caller gave no ``otherwise``")
    return otherwise()
