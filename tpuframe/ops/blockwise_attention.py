"""Blockwise (flash-style) single-device attention: linear-in-L memory.

Ring attention (`tpuframe.ops.ring_attention`) spreads the sequence over
chips; this is the within-one-shard counterpart for long context that
FITS on a chip but whose (B, H, L, L) score matrix would not — forward
AND backward.  One arithmetic, two schedules of it:

- **The Pallas flash kernels** (``tpuframe_flash_fwd`` and
  ``tpuframe_flash_bwd``) wherever a kernel can run: a single-device
  TPU process or a manual region (a caller's ``shard_map`` over the
  batch and the heads), and anywhere in Pallas interpret mode
  (``TPUFRAME_PALLAS_INTERPRET=1`` or ``interpret=True``).  The score
  tile, the probabilities and the running (output, sum, max) state live
  in VMEM; HBM sees q, k, v, the output and the rows' logsumexp, once
  each, in the model's own (B, L, H*D) rows wherever heads make a block
  of their lanes (128-wide heads several a block, 64- or 32-wide ones
  two or four in 128 lanes; a 192-wide head goes through a heads-first
  copy).  The kernels are bound by the MXU, not by bandwidth.
- **The scan schedule** (:func:`blockwise_attention_reference`)
  everywhere else — CPU, ``TPUFRAME_DISABLE_PALLAS``, a multi-device
  jit without a mesh, a
  sequence whose dQ of one head outgrows VMEM (`_bwd_vmem_bytes`: past
  ~32k positions of 192-wide bf16 rows) — and as what the kernels are
  held to.  It is made of
  ``ring_attention._block_update`` / ``_tile_grads`` / ``_causal_skip``,
  which ring attention's own sweep shares.

Both:

- **Forward**: for every Q block, an online-softmax sweep over the K/V
  blocks emits, besides the normalized output, each row's logsumexp.
- **Backward**: hand-written (``jax.custom_vjp``), the FlashAttention-2
  two-pass recipe.  Reverse-mode through the scan-of-scans stacked
  per-step residuals and re-ran the whole inner sweep per Q block —
  measured 107.6 ms fwd+bwd per layer at seq 8192 on v5e vs 13.0 ms
  forward (PERF.md r03).  Instead the VJP saves only Q/K/V, the output
  and the O(L) logsumexp, and recomputes probabilities one
  (block x block) tile at a time: pass 1 sweeps the K/V blocks past
  each Q block accumulating dQ; pass 2 sweeps the Q blocks past each
  K/V block accumulating dK/dV.  The backward kernel makes it one
  pass: pass 2's sweep, each tile's dS also adding into its rows of a
  head's float32 dQ, which stays in VMEM.
- Q/K/V keep their storage dtype end to end: the MXU multiplies bf16
  natively with f32 accumulation; only softmax state (and the gradient
  accumulators) are f32.
- L pads up to a block multiple (padded keys are masked via ``kv_len``,
  padded query rows are sliced off) — one MXU-friendly compiled
  schedule for any L, never a degenerate tiny-block divisor.  The
  schedule's block is ``_SCAN_BLOCK``; the kernels' tiles follow L
  alone (`_tiles`).
- Causal: tiles entirely above the diagonal are *skipped at runtime*,
  so the sweep executes only the tiles that meet the triangle; the
  tiles the diagonal crosses mask element-wise.  The scan bodies branch
  on the scalar block indices with ``lax.cond`` (a real XLA
  Conditional, not a select); the kernels' skip is their own
  (``pl.when``, and an index map that fetches nothing for a skipped
  tile).

- A mask that is neither causal nor full is a rule on positions
  (``mask=``: the protocol `ring_attention` states beside its two rules,
  `BlockDiffusionMask` and `SlidingWindowMask`, a few static integers):
  `_tile_classes` sorts the tiles into dead, whole and masked once, at
  trace time; the scan schedule skips by that table, and the kernels run
  a grid that holds the live tiles alone (`_tile_plan`: per held block
  the streamed blocks to visit, read by the ``index_map`` from scalar
  memory), so a dead tile costs neither a fetch nor a grid step.  The
  kernels' names carry the rule's ``suffix`` in a trace
  (``tpuframe_flash_fwd_window``); a rule that is the causal mask on
  the row at hand runs as ``causal`` (`ring_attention.mask_or_causal`).
  A rule may also read operands (``mask_operands=``: arrays the model
  computes, one byte a (query, key) pair at most and shared by the
  heads; `SelectedKeysMask` reads the keys a learned index chose): the
  scan schedule scans a block of each with the K/V blocks, the kernels
  fetch a (side, side) tile of each a grid step, and the tile plan is
  what the rule can say before the operand exists.
- Grouped heads: ``k`` and ``v`` may hold one head a group of query
  heads.  They stay that size in HBM; the kernels' ``index_map`` reads
  head ``j // group``, and dK/dV come out a query head and are summed
  over the group by one pass after the backward kernel.

``TransformerLM(attn_impl="blockwise")`` selects it, and ``"auto"``
does wherever the kernels would run (:func:`engage_kernels`, the one
predicate this op and that rule ask) and, for memory, from 4096
unsharded positions on whatever schedule runs.  On a mesh the caller
places the op per shard under ``shard_map`` (whole rows and heads a
device: ``models/transformer.py::_attend``), where the kernels engage
as in any manual region; it composes with the ``seq``-sharded impls
(they shard ACROSS devices, this blocks WITHIN one).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuframe.ops.dispatch import pad_to, resolve_interpret
from tpuframe.ops.registry import shape_class
from tpuframe.ops.ring_attention import (
    _block_update,
    _causal_skip,
    _repeat_kv,
    _tile_grads,
    mask_or_causal,
    pad_operands,
    rule_operands,
)

__all__ = ["blockwise_attention", "blockwise_attention_reference",
           "engage_kernels", "tile_counts"]

_LANES = 128
#: the scan schedule's block where the caller names none (lane-aligned)
_SCAN_BLOCK = 512
#: the kernels' largest tile side: a float32 (1024, 1024) score tile is
#: 4 MiB, and the backward holds four such
_MAX_TILE = 1024
#: what the backward kernel's tiles may take of VMEM at that side
#: (scores, probabilities, dP, dS; the double-buffered blocks; dK, dV)
_TILE_VMEM_BYTES = 32 << 20
#: what the backward kernel may ask of a core's 128 MiB of VMEM
_VMEM_BYTES = 100 << 20
#: heads of whole lanes that share a block of the model's rows, at most
_WIDE_HEADS = 4


def _to_blocks(a, n, block):
    b, _, h, d = a.shape
    return a.reshape(b, n, block, h, d).transpose(1, 0, 2, 3, 4)


def _from_blocks(a):
    n, b, block, h, d = a.shape
    return a.transpose(1, 0, 2, 3, 4).reshape(b, n * block, h, d)


@functools.lru_cache(maxsize=64)
def _tile_classes(rule, l_pad, side, kv_len):
    """(n, n) numpy, [query tile, key tile]: 0 a tile with no score that
    counts, 1 one with no other, 2 one to mask element-wise, for square
    tiles of ``side`` over ``l_pad`` positions with keys up to ``kv_len``."""
    lo = np.arange(l_pad // side) * side
    hi = lo + side - 1
    live, whole = rule.tiles(lo[:, None], hi[:, None], lo[None, :], hi[None, :])
    live = live & (lo < kv_len)[None, :]
    return np.where(live, np.where(whole & (hi < kv_len)[None, :], 1, 2), 0)


def _tile_live(causal, n, block, kv_len):
    """The scan schedule's skip: ``live(q_idx, k_idx)``, a scalar bool or
    None (every tile runs)."""
    if causal is True:
        return lambda q_idx, k_idx: k_idx <= q_idx
    if not causal:
        return lambda q_idx, k_idx: None
    table = jnp.asarray(_tile_classes(causal, n * block, block, kv_len) > 0)
    return lambda q_idx, k_idx: table[q_idx, k_idx]


def _query_rows(a, n, block):
    """A rule's operand (B, L, L) as the rows of each Q block: (n, B, block, L)."""
    return a.reshape(a.shape[0], n, block, -1).transpose(1, 0, 2, 3)


def _key_columns(a, n, block):
    """... as the columns of each K/V block: (n, B, L, block)."""
    return a.reshape(a.shape[0], -1, n, block).transpose(2, 0, 1, 3)


def _fwd_schedule(q_blocks, k_blocks, v_blocks, causal, scale, block, kv_len,
                  operands=()):
    """Online-softmax forward over blocks -> (out_blocks, lse_blocks)."""
    n, b, _, h, _ = q_blocks.shape
    dv = v_blocks.shape[-1]  # the output takes the values' width
    block_pos = jnp.arange(block)
    live = _tile_live(causal, n, block, kv_len)

    def q_body(q_blk, q_idx, rows):
        q_pos = q_idx * block + block_pos
        init = (
            jnp.zeros((b, block, h, dv), jnp.float32),
            jnp.zeros((b, h, block), jnp.float32),
            jnp.full((b, h, block), -jnp.inf, jnp.float32),
        )

        def kv_body(carry, xs):
            k_blk, v_blk, k_idx, blocks = xs

            def update(c):
                return _block_update(
                    q_blk, k_blk, v_blk, *c,
                    q_pos, k_idx * block + block_pos,
                    causal, scale, kv_len=kv_len, blocks=blocks,
                )

            # tiles entirely above the diagonal are SKIPPED at runtime,
            # not just masked — ~half the causal sweep never executes
            carry = _causal_skip(live(q_idx, k_idx), update, carry)
            return carry, None

        (o, lsum, m), _ = lax.scan(
            kv_body, init, (k_blocks, v_blocks, jnp.arange(n),
                            tuple(_key_columns(a, n, block) for a in rows))
        )
        lsum = jnp.maximum(lsum, 1e-30)  # fully-masked (padded/causal) rows
        # logsumexp per row: -inf rows stay -inf (m = -inf dominates)
        lse = m + jnp.log(lsum)
        # downcast BEFORE the scan stacks ys: the stacked (n, B, blk, H,
        # D) buffer is written+re-read once per layer, and f32 would
        # double that traffic on this memory-bound path
        out = (o / lsum.transpose(0, 2, 1)[..., None]).astype(q_blocks.dtype)
        return out, lse

    _, (outs, lses) = lax.scan(
        lambda _, xs: (None, q_body(*xs)), None,
        (q_blocks, jnp.arange(n), tuple(_query_rows(a, n, block) for a in operands))
    )
    return outs, lses  # (n, B, blk, H, D) storage dtype, (n, B, H, blk) f32


# -- the Pallas flash kernels --------------------------------------------------
#
# Same tiles, same arithmetic as the schedule above.  A kernel works on
# one head's (block, D) tile of each array at a time, and where that
# tile comes from follows the head's width (`_in_place`, `_chunk_heads`).
# Whole lanes wide (128): a lane-aligned slice of a block of the model's
# own folded rows (B, L, H*D), `_WIDE_HEADS` heads a block, picked out of
# the last axis by the block index.  Half or a quarter of the lanes wide
# (64, 32): two or four heads share a 128-lane block of those rows and a
# head's lanes are picked by a mask (the MXU contracts over all 128
# lanes for a narrow head anyway).  Either way the kernel runs a block's
# heads in turn, one grid step for all of them.  Any other width (192 is
# no lane multiple, so no block of the rows can hold one head) is read
# from a heads-first (B, H, L, D) copy, where a head's tile is a
# contiguous row range and a legal Mosaic block at any width.  Every
# kernel runs a grid (batch, block of heads, held block, streamed
# block), the last axis sequential: the held side's blocks stay in VMEM
# while the other side streams past, and the float32 state (output
# accumulator, row sum, row max; dQ; dK and dV) lives in VMEM scratch and
# reaches HBM once a held block.  The causal skip is the kernels' own: a
# tile above the diagonal runs nothing (``pl.when``) and fetches nothing
# (the streamed ``index_map`` is clamped to the diagonal, so a skipped
# step names the block that is already resident).  Row statistics travel
# as (B, H, 1, L) rows: a (B, H, L, 1) column would be padded 128-fold in
# HBM.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T, the contraction on both last axes


def _precision(a):
    """Narrow operands multiply exactly on the MXU in one pass; Mosaic
    takes no other precision for them (an ambient ``highest`` is for the
    float32 operands it was set for)."""
    return lax.Precision.DEFAULT if a.dtype.itemsize < 4 else None


def _nt_dot(a, b):
    return lax.dot_general(a, b, _NT, precision=_precision(a),
                           preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, precision=_precision(a),
                   preferred_element_type=jnp.float32)


def _eye(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col_to_row(col):
    """(n, 1) -> (1, n) by a masked sublane sum: once a held block, and
    nothing but a select and a reduction for Mosaic to lay out."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0, keepdims=True)


def _is_rule(causal) -> bool:
    """``causal`` as the schedules carry it: a bool, or a rule on
    positions in its place (`ring_attention`'s protocol)."""
    return not isinstance(causal, bool)


def _valid(q_idx, k_idx, *, side, causal, kv_len, keys_first=False, **_):
    """Which scores of tile (q_idx, k_idx) count: keys before ``kv_len``
    and, if causal, not after their query (a rule on positions: as it
    says).  (side, side) bool, queries along the rows (``keys_first``:
    keys along the rows)."""
    if _is_rule(causal):
        # each side coded along its own axis, a column and a row
        along = lambda axis: lax.broadcasted_iota(  # noqa: E731
            jnp.int32, (side, 1) if axis == 0 else (1, side), axis)
        return causal.allowed(q_idx * side + along(int(keys_first)),
                              k_idx * side + along(int(not keys_first)), kv_len)
    q_pos = q_idx * side + lax.broadcasted_iota(
        jnp.int32, (side, side), int(keys_first))
    k_pos = k_idx * side + lax.broadcasted_iota(
        jnp.int32, (side, side), int(not keys_first))
    valid = k_pos < kv_len
    return valid & (k_pos <= q_pos) if causal else valid


def _valid_by_operands(blocks, *, causal, kv_len, keys_first=False, **_):
    """`_valid` under a rule that reads operands, from the tile of each
    ((queries, keys) refs): the same for every head of a block, so a grid
    step asks once.  Turned here where the keys come first, as the 32-bit
    values Mosaic transposes."""
    blocks = [ref[...].astype(jnp.int32) for ref in blocks]
    return causal.allowed(None, None, kv_len, *(a.T if keys_first else a for a in blocks))


def _visit(q_idx, k_idx, update, kind=None, *, causal, side, kv_len, l_pad, **_):
    """``update(masked)`` on tile (q_idx, k_idx) if it holds a score that
    counts; ``masked`` only where it also holds one to mask: the tiles
    on the diagonal and the K blocks that reach into the padding.  Under
    a rule on positions the tile's ``kind`` says which (`_tile_classes`)."""
    if kind is not None:
        pl.when(kind == 2)(lambda: update(True))
        pl.when(kind == 1)(lambda: update(False))
        return
    live, edges = [], []
    if causal:
        live.append(k_idx <= q_idx)
        edges.append(k_idx == q_idx)
    if kv_len < l_pad:
        live.append(k_idx * side < kv_len)
        edges.append((k_idx + 1) * side > kv_len)
    if not edges:
        update(False)
        return
    live = functools.reduce(jnp.logical_and, live)
    masked = functools.reduce(jnp.logical_or, edges)
    pl.when(live & masked)(lambda: update(True))
    pl.when(live & jnp.logical_not(masked))(lambda: update(False))


# No row of a tile sweep is ever wholly masked: key 0 lies before
# ``kv_len`` and not after any query, and its block is the first a held
# Q block meets.  So the running max is finite from the first update on
# and every logsumexp is finite, and the guards `_block_update` and
# `_tile_grads` carry for rows that have seen nothing yet (ring
# attention hands them whole blocks of such rows) would select their
# other branch nowhere; the kernels leave them out, bit for bit the same.
# Under a rule on positions every row still has a key (a noised query
# its own block, a clean one itself), so every logsumexp is finite; but
# the first tile a held Q block meets may hold rows that see nothing in
# it (a tile that straddles the two copies), and the forward's masked
# update keeps the running max of such a row out of the exponent.


def _step(refs, width):
    """One grid step's (refs, held block, streamed block, kind of tile).
    Under a rule on positions the first two refs are the plan in scalar
    memory (`_tile_plan`) and the last grid axis counts the held block's
    live tiles; else it is the streamed block's own index."""
    held, at = pl.program_id(2), pl.program_id(3)
    if width is None:
        return refs, held, at, None
    order, kinds, *refs = refs
    return refs, held, order[held * width + at], kinds[held * width + at]


def _chunk(heads, group, narrow):
    """The heads of one block of lanes, for the kernels' static loop:
    ``(load, part, only, update)``.  A block holds one head (``heads``
    1: everything below is the identity), ``heads`` heads narrower than
    the lanes side by side in 128 of them (``narrow``), or ``heads``
    heads of whole lanes each.

    ``load(ref, kv=False)`` then ``part(x, i)`` give head i its operand:
    a lane-aligned slice of the ref where heads are whole lanes wide;
    the whole 128-lane block where they are narrow, which ``only(x, i)``
    then zeroes outside head i's lanes (a product over all 128 lanes
    with one such operand is head i's own: the MXU contracts over whole
    lanes for a narrow head anyway).  ``kv``: a K/V block.  Where
    ``group`` query heads share a K/V head (in whole blocks), a block of
    wide heads brings that one head alone, and a narrow one finds it
    among the block's heads by the grid's head index and repeats it in
    every head's lanes.  ``update(ref, i, fn, rows)`` replaces head i's
    lanes of ``ref[rows]`` by ``fn`` of them."""
    everything = slice(None)

    def update_all(ref, i, fn, rows=everything):
        ref[rows, :] = fn(ref[rows, :])

    same = lambda x, i, kv=False: x  # noqa: E731
    if heads == 1:
        return (lambda ref, kv=False: ref[...]), same, same, update_all
    if not narrow:
        def lanes(ref, i):
            d = ref.shape[-1] // heads
            return slice(i * d, (i + 1) * d)

        def part(ref, i, kv=False):
            # a K/V head a group: the block is that head
            return ref[...] if kv and group > 1 else ref[:, lanes(ref, i)]

        def update(ref, i, fn, rows=everything):
            ref[rows, lanes(ref, i)] = fn(ref[rows, lanes(ref, i)])

        return (lambda ref, kv=False: ref), part, same, update
    d = _LANES // heads
    of = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // d
    mine = [of == i for i in range(heads)]
    at = (pl.program_id(1) // max(group // heads, 1)) % heads

    def load(ref, kv=False):
        x = ref[...]
        if not kv or group == 1:
            return x
        # Mosaic rotates 32-bit lanes only
        one = jnp.where(of == at, x, jnp.zeros_like(x)).astype(jnp.float32)
        return functools.reduce(
            jnp.add, [one] + [pltpu.roll(one, r * d, 1) for r in range(1, heads)]
        ).astype(x.dtype)

    def update(ref, i, fn, rows=everything):
        old = ref[rows, :]
        ref[rows, :] = jnp.where(mine[i], fn(old), old)

    return load, same, lambda x, i: jnp.where(mine[i], x, jnp.zeros_like(x)), update


def _fwd_kernel(*refs, width, heads, group, narrow, reads, **tile):
    """`_block_update` over the K/V blocks streaming past one Q block,
    a head of the block at a time (`_chunk`); ``reads`` operand tiles of
    the rule follow q, k and v."""
    (q_ref, k_ref, v_ref, *rest), q_idx, k_idx, kind = _step(refs, width)
    blocks, (o_ref, lse_ref, acc_ref, l_ref, m_ref) = rest[:reads], rest[reads:]
    load, part, only, update = _chunk(heads, group, narrow)

    @pl.when(pl.program_id(3) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)

    def step(masked):
        qs, ks, vs = load(q_ref), load(k_ref, kv=True), load(v_ref, kv=True)
        chosen = _valid_by_operands(blocks, **tile) if masked and blocks else None
        for i in range(heads):
            v = part(vs, i, kv=True)
            # (queries, keys) f32
            s = _nt_dot(only(part(qs, i), i), part(ks, i, kv=True)) * tile["scale"]
            if masked:
                s = jnp.where(_valid(q_idx, k_idx, **tile) if chosen is None else chosen,
                              s, -jnp.inf)
            m = m_ref[i]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            m_exp = m_new
            if masked and kind is not None:  # a row that has seen no key yet
                m_exp = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(s - m_exp)
            correction = jnp.exp(m - m_exp)
            l_ref[i] = l_ref[i] * correction + jnp.sum(p, axis=1, keepdims=True)
            update(acc_ref, i, lambda acc: acc * correction + _dot(p.astype(v.dtype), v))
            m_ref[i] = m_new

    _visit(q_idx, k_idx, step, kind, **tile)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _():
        accs = load(acc_ref)
        for i in range(heads):
            lsum = jnp.maximum(l_ref[i], 1e-30)
            update(o_ref, i, lambda _: (part(accs, i) / lsum).astype(o_ref.dtype))
            lse_ref[i] = _col_to_row(m_ref[i] + jnp.log(lsum))


def _bwd_kernel(*refs, width, heads, group, narrow, reads, **tile):
    """`_tile_grads` and the three products over the Q blocks streaming
    past one K/V block: the schedule's two passes in one, a head of the
    block at a time (`_chunk`).  The tile is
    held keys-first (scores transposed): the row statistics then
    broadcast along the rows as they arrive, and the dK/dV products
    contract over the tile's last axis, nothing transposed.  Each tile's
    dS also goes, transposed by the product itself, into its Q block's
    rows of a dQ accumulator that spans the sequence and is written once
    a head: five tile products and one ``exp`` where two passes take
    seven and two."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest), k_idx, q_idx, kind = _step(
        refs, width)
    blocks, (dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc) = rest[:reads], rest[reads:]
    first, last = pl.program_id(3) == 0, pl.program_id(3) == pl.num_programs(3) - 1
    side = tile["side"]
    load, part, only, update = _chunk(heads, group, narrow)

    @pl.when((k_idx == 0) & first)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        qs, dos = load(q_ref), load(do_ref)
        ks, vs = load(k_ref, kv=True), load(v_ref, kv=True)
        rows = pl.ds(pl.multiple_of(q_idx * side, side), side)
        chosen = (_valid_by_operands(blocks, keys_first=True, **tile)
                  if masked and blocks else None)
        for i in range(heads):
            q, do, k = part(qs, i), part(dos, i), part(ks, i, kv=True)
            s = _nt_dot(only(k, i), q) * tile["scale"]  # (keys, queries) f32
            if masked:
                s = jnp.where(
                    _valid(q_idx, k_idx, keys_first=True, **tile) if chosen is None else chosen,
                    s, -jnp.inf)
            p = jnp.exp(s - lse_ref[i])
            update(dv_acc, i, lambda dv: dv + _dot(p.astype(do.dtype), do))
            dp = _nt_dot(only(part(vs, i, kv=True), i), do)
            ds = (p * (dp - delta_ref[i]) * tile["scale"]).astype(q.dtype)
            update(dk_acc, i, lambda dk: dk + _dot(ds, q))
            update(dq_acc, i, lambda dq: dq + lax.dot_general(  # ds.T @ k
                ds, k, (((0,), (0,)), ((), ())), precision=_precision(ds),
                preferred_element_type=jnp.float32), rows)

    _visit(q_idx, k_idx, step, kind, **tile)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((k_idx == pl.num_programs(2) - 1) & last)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _in_place(width):
    """The chunk rule for one array, from its head width alone: a head
    that is whole lanes wide is a legal Mosaic block of the model's
    folded rows (B, L, H*D), picked out of the last axis by the block's
    index; any other width (192) is read from a heads-first copy."""
    return width % _LANES == 0


def _chunk_heads(h, kv_heads, d, dv, l_pad):
    """How many heads one block of lanes holds for a call's kernels, every
    array then in place.  Heads narrower than the lanes by a whole factor
    (64: two) run side by side in 128 lanes where q, k and v are one
    width; heads of whole lanes (128) run `_WIDE_HEADS` a block where a
    block's dQ over ``l_pad`` positions still fits VMEM: fewer, longer
    grid steps and longer runs in HBM.  Either way the heads and the K/V
    heads come in whole blocks (a group of query heads over one K/V head
    likewise); else 1, and each array goes by its own width
    (`_in_place`)."""
    group = h // kv_heads
    wide = _in_place(d) and _in_place(dv)
    if wide:
        n = _WIDE_HEADS  # reckoned for float32 rows, the widest the kernels take
        while n > 1 and _bwd_vmem_bytes(l_pad, n * d, jnp.float32) > _VMEM_BYTES:
            n //= 2
    else:
        n = _LANES // d if d == dv and _LANES % d == 0 else 1

    def whole(n):
        return h % n == 0 and (
            group == 1 or group % n == 0 and (wide or kv_heads % n == 0))

    while wide and not whole(n):
        n //= 2
    return n if whole(n) else 1  # narrow heads fill the 128 lanes or go by copies


def layout_counts(h, kv_heads, d, dv):
    """(in place, copied): how many of the eleven arrays a layer's two
    kernels read and write (q, k, v and the output; q, k, v, dO, dQ, dK,
    dV) stay in the model's rows, by the chunk rule.  Row statistics
    are not counted."""
    if _chunk_heads(h, kv_heads, d, dv, 0) > 1:
        return 11, 0
    in_place = 6 * _in_place(d) + 5 * _in_place(dv)
    return in_place, 11 - in_place


def _flash_call(kernel, name, operands, outs, scratch, *, streams, side,
                causal, scale, kv_len, interpret, chunk=1,
                held_axis="parallel", vmem_bytes=None):
    """One kernel of the family over grid (batch, head, held, streamed),
    in square tiles of ``side`` positions.

    ``operands`` and ``outs`` are (array or ShapeDtypeStruct, role) pairs
    in the model's layout (B, L, H, D), and the results come back in it:
    role ``"q"`` / ``"k"`` says which side's block index the array
    follows (``"all"``: a head's whole sequence, resident); ``"stat"`` is
    a (B, H, 1, L) row statistic of the queries; ``"mask"`` an operand of
    the rule, (B, L, L) with [row, query, key], read a (side, side) tile
    a grid step whichever side streams.  Each array is handed
    to the kernel by its own head width (`_in_place`): the folded rows
    (B, L, H*D) as they are, a block a head's lanes, or a heads-first
    (B, H, L, D) copy; the kernel sees a (positions, D) block either
    way, and a call may mix both.  ``chunk`` > 1 (`_chunk_heads`): the
    grid's head axis counts blocks of that many heads, every array in
    its rows, and the kernel runs a block's heads in turn (`_chunk`).
    ``scratch`` lists the shapes of the float32 VMEM scratch.
    ``streams`` names the side that moves along the last grid axis;
    under a causal mask its index is clamped to the diagonal, from above for keys (tiles past it) and from below
    for queries (tiles before it), so a step that computes nothing
    fetches nothing; under a rule on positions the last grid axis counts
    a held block's live tiles and the index comes from `_tile_plan`.  An
    operand with fewer heads than the first is read a group of heads at
    a time (head ``j // group``); a result that asks for fewer is written
    a query head and summed over the group after the kernel.
    ``vmem_bytes`` replaces Mosaic's 16 MiB of scoped VMEM."""
    b, l_pad, h, _ = operands[0][0].shape
    if l_pad % side:  # a floored grid would leave rows unvisited
        raise ValueError(f"tiles of {side} do not divide {l_pad} padded positions")
    clamp = {"k": jnp.minimum, "q": jnp.maximum}[streams]
    n = l_pad // side
    plan, width = (), None
    if _is_rule(causal):
        *plan, width = _tile_plan(causal, l_pad, side, kv_len, streams)

    def block_index(role, held, streamed, plan):
        if role == "all":
            return 0
        if ("q" if role == "stat" else role) != streams:
            return held
        if plan:
            return plan[0][held * width + streamed]
        return clamp(streamed, held) if causal else streamed

    narrow = chunk > 1 and not _in_place(operands[0][0].shape[3])
    reads = sum(role == "mask" for _, role in operands)

    def rows_of(a):
        return chunk > 1 or _in_place(a.shape[3])

    def spec(a, role, heads):
        if role == "mask":
            return pl.BlockSpec(
                (None, side, side),
                lambda b_, h_, held, streamed, *plan: (
                    b_, block_index("q", held, streamed, plan),
                    block_index("k", held, streamed, plan)))
        if role == "stat":
            return pl.BlockSpec(
                (None, chunk, 1, side),
                lambda b_, h_, held, streamed, *plan: (
                    b_, h_, 0, block_index(role, held, streamed, plan)))
        group, rows = h // heads, rows_of(a)
        # the heads a block of this array holds: the grid step's, but of
        # wide heads a group shares the one K/V head
        per = chunk if narrow or group == 1 else 1
        d = a.shape[3] * per

        def at(b_, h_, held, streamed, *plan):
            idx = block_index(role, held, streamed, plan)
            head = h_ * chunk // group // per
            return (b_, idx, head) if rows else (b_, head, idx, 0)

        length = l_pad if role == "all" else side
        return pl.BlockSpec(
            (None, length, d) if rows else (None, None, length, d), at)

    def placed(a, role):
        """An operand as the kernel takes it: its rows, or the copy."""
        if role in ("stat", "mask"):
            return a
        return a.reshape(b, l_pad, -1) if rows_of(a) else _heads_first(a)

    def written(a, role):
        """What the kernel writes for result ``a``: a block a query head."""
        if role == "stat":
            return a
        d = a.shape[3]
        return jax.ShapeDtypeStruct(
            (b, l_pad, h * d) if rows_of(a) else (b, h, l_pad, d), a.dtype)

    def returned(result, a, role):
        """A result in the model's layout, a group of heads summed where
        ``a`` asks for fewer than the kernel wrote."""
        if role == "stat":
            return result
        heads, d = a.shape[2:]
        if not rows_of(a):
            if heads != h:
                result = jnp.sum(result.reshape(b, heads, -1, l_pad, d), axis=2,
                                 dtype=jnp.float32).astype(a.dtype)
            return _heads_first(result)
        if heads != h:
            # a head's lanes added as they lie: a reduction over a group
            # axis would lay the rows out heads-apart first
            group = h // heads
            lanes = lambda j: result[..., j * d:(j + 1) * d].astype(jnp.float32)  # noqa: E731
            result = jnp.concatenate(
                [sum(lanes(j * group + i) for i in range(group)) for j in range(heads)],
                axis=-1).astype(a.dtype)
        return result.reshape(a.shape)

    grid = dict(
        grid=(b, h // chunk, n, width or n),
        in_specs=[spec(a, role, a.shape[2] if a.ndim == 4 else h) for a, role in operands],
        out_specs=tuple(spec(a, role, h) for a, role in outs),
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
    )
    if plan:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan), **grid))
    results = pl.pallas_call(
        functools.partial(kernel, causal=causal, scale=scale, side=side,
                          kv_len=kv_len, l_pad=l_pad, width=width, heads=chunk,
                          group=h // operands[1][0].shape[2], narrow=narrow, reads=reads),
        **grid,
        out_shape=tuple(written(a, role) for a, role in outs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", held_axis, "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name=name,
    )(*(jnp.asarray(p) for p in plan), *(placed(a, role) for a, role in operands))
    return tuple(returned(r, a, role) for r, (a, role) in zip(results, outs))


@functools.lru_cache(maxsize=64)
def _tile_plan(rule, l_pad, side, kv_len, streams):
    """(order, kinds, width) for a kernel's grid under a rule on
    positions: per held block (a Q block if the keys stream, a K/V block
    if the queries do) the ``width`` streamed blocks to visit, in rising
    order, and the kind of each tile (`_tile_classes`); both flat int32,
    ``[held * width + step]``.  A held block with fewer live tiles names
    its last one again with kind 0: nothing fetched, nothing run."""
    kinds = _tile_classes(rule, l_pad, side, kv_len)
    if streams == "q":
        kinds = kinds.T
    width = max(int((kinds > 0).sum(axis=1).max()), 1)
    order = np.zeros((len(kinds), width), np.int32)
    kind = np.zeros((len(kinds), width), np.int32)
    for held, row in enumerate(kinds):
        live = np.flatnonzero(row)
        if live.size:
            order[held] = live[-1]
            order[held, :live.size] = live
            kind[held, :live.size] = row[live]
    return order.reshape(-1), kind.reshape(-1), width


def _tiles(l, block):
    """The side of the forward's square tiles and of the backward's for
    ``l`` positions; the forward's is a multiple of the backward's, so
    both divide ``l`` padded to it.  ``block`` None: the tiles follow
    ``l`` alone, 512 a side, the forward's 1024 where that pads no
    further: what it does once a tile beside the products (the row
    maxima and sums across lanes, rescaling the accumulator) then weighs
    half as much, which bought 1.5x at 4096 positions; the backward
    carries no such state and ran alike from 512 up, half as fast at 256
    (v5e, PR 28).  An explicit ``block`` is every tile's side, in whole
    lanes and no more than `_MAX_TILE`."""
    lanes = pad_to(l, _LANES)
    if block is not None:
        side = min(pad_to(block, _LANES), lanes, _MAX_TILE)
        return side, side
    side = min(512, lanes)
    return (2 * side if pad_to(l, side) % _MAX_TILE == 0 else side), side


def _bwd_vmem_bytes(l_pad, d, dtype):
    """What the backward kernel holds in VMEM: its tiles, and a head's
    dQ over the whole sequence as the float32 accumulator and, twice
    (Mosaic double-buffers it), the output block."""
    return _TILE_VMEM_BYTES + l_pad * pad_to(d, _LANES) * (
        4 + 2 * jnp.dtype(dtype).itemsize)


# Both kernels are jitted for themselves: a model's layers then share one
# trace and one lowering of each (a pallas_call is traced and lowered to
# Mosaic anew at every call site otherwise, and a kernel body that runs
# several heads is that many times the text: 48 of them are seconds of a
# warm first step).  ``causal`` is static, so a model whose layers run
# under different rules holds one trace a rule, not one a layer; under a
# rule the kernel's name carries the rule's ``suffix``.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_fwd(q, k, v, causal, scale, side, kv_len, interpret, operands=()):
    """(B, L, H, D) q/k, (B, L, H, Dv) v -> out (B, L, H, Dv) in the
    storage dtype and the rows' logsumexp (B, H, 1, L) float32."""
    (b, l_pad, h, d), dv = q.shape, v.shape[-1]
    n = _chunk_heads(h, k.shape[2], d, dv, l_pad)
    return _flash_call(
        _fwd_kernel, "tpuframe_flash_fwd" + getattr(causal, "suffix", ""),
        [(q, "q"), (k, "k"), (v, "k")] + [(a, "mask") for a in operands],
        [(jax.ShapeDtypeStruct((b, l_pad, h, dv), q.dtype), "q"),
         (jax.ShapeDtypeStruct((b, h, 1, l_pad), jnp.float32), "stat")],
        [(side, n * dv), (n, side, 1), (n, side, 1)],
        streams="k", side=side, chunk=n,
        # a block of several heads outgrows Mosaic's 16 MiB: the tiles, and
        # the blocks' buffers and statistics a head
        vmem_bytes=None if n == 1 else 2 * _TILE_VMEM_BYTES,
        causal=causal, scale=scale, kv_len=kv_len, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_bwd(q, k, v, do, lse, delta, causal, scale, side, kv_len, interpret,
               operands=()):
    """dQ, dK, dV in the layout (B, L, H, D) and the dtypes of q, k, v.
    ``lse`` and ``delta`` (rowsum(dO . O)) are (B, H, 1, L) rows."""
    (b, l_pad, h, d), dv = q.shape, v.shape[-1]
    n = _chunk_heads(h, k.shape[2], d, dv, l_pad)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dk, dv_, dq = _flash_call(
        _bwd_kernel, "tpuframe_flash_bwd" + getattr(causal, "suffix", ""),
        [(q, "q"), (k, "k"), (v, "k"), (do, "q"), (lse, "stat"), (delta, "stat")]
        + [(a, "mask") for a in operands],
        [(like(k), "k"), (like(v), "k"), (like(q), "all")],
        [(side, n * d), (side, n * dv), (l_pad, n * d)], streams="q", side=side,
        chunk=n, held_axis="arbitrary", vmem_bytes=_bwd_vmem_bytes(l_pad, n * d, q.dtype),
        causal=causal, scale=scale, kv_len=kv_len, interpret=interpret,
    )
    return dq, dk, dv_


def _heads_first(a):
    """(B, L, H, D) <-> (B, H, L, D): the model's layout and the one the
    kernels read a head from where its width is no whole lanes."""
    return a.transpose(0, 2, 1, 3)


def _row_delta(out, g, heads):
    """delta_i = rowsum(dO . O), the softmax-normalization term of dS, as
    the (B, H, 1, L) float32 rows the backward kernel reads, from ``out``
    and ``g`` as they lie: the folded rows (B, L, H*D) in their own
    dtype.  The float32 products are summed a head by a product with a
    0/1 matrix (H*D, H) on the MXU, which XLA fuses with the casts and
    writes heads-first: a reduction over D would lay a float32
    (B, L, H, D) copy out heads-apart first, three passes over four
    bytes an element.  The MXU takes bfloat16, so the float32 product
    goes in as bfloat16 terms, each the rounding of what the terms
    before left: two hold the 16 significant bits of a product of
    bfloat16 values exactly, three a float32 product's 24; the
    accumulation is float32."""
    products = out.astype(jnp.float32) * g.astype(jnp.float32)
    width = products.shape[-1]
    of_head = (jnp.arange(width)[:, None] // (width // heads)
               == jnp.arange(heads)[None, :]).astype(jnp.float32)
    delta = 0.0
    for _ in range(2 if out.dtype == jnp.bfloat16 else 3):
        # float32 operands that bfloat16 holds exactly, in one MXU pass
        # whatever the ambient precision (XLA:CPU has no bfloat16 dot)
        term = products.astype(jnp.bfloat16).astype(jnp.float32)
        delta = delta + jnp.einsum("blk,kh->bhl", term, of_head,
                                   precision=lax.Precision.DEFAULT)
        products = products - term
    return delta[:, :, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _blockwise_padded(q, k, v, operands, causal, block, kv_len, scale, interpret):
    out, _ = _blockwise_padded_fwd(q, k, v, operands, causal, block, kv_len, scale, interpret)
    return out


def _blockwise_padded_fwd(q, k, v, operands, causal, block, kv_len, scale, interpret):
    """``interpret`` None: the scan schedule over blocks of ``block``;
    else the kernels (True: in Pallas interpret mode), ``block`` their
    (forward, backward) `_tiles`, both dividing the padded length.
    Either way the residuals are q, k, v, the output and the rows'
    logsumexp, each held once, and the rule's ``operands`` (padded, no
    gradient) where it reads any."""
    if interpret is not None:
        out, lse = _flash_fwd(q, k, v, causal, scale, block[0], kv_len, interpret, operands)
        # kept with the heads folded into the rows, (B, L, H*D), as the
        # projections made them: whole lanes at any head width, and what
        # the kernels read in place where a head is whole lanes wide (XLA
        # lays a (B, L, H, D) array kept for the backward out padded: a
        # 192-wide row to 256 lanes, a 64-wide one to 128).
        return out, (*(a.reshape(*a.shape[:2], -1) for a in (q, k, v, out)), lse, operands)
    b, l_pad, h, d = q.shape
    n = l_pad // block
    outs, lses = _fwd_schedule(
        _to_blocks(q, n, block), _to_blocks(k, n, block),
        _to_blocks(v, n, block), causal, scale, block, kv_len, operands,
    )
    out = _from_blocks(outs).astype(q.dtype)
    return out, (q, k, v, out, lses, operands)


def _blockwise_padded_bwd(causal, block, kv_len, scale, interpret, res, g):
    q, k, v, out, lses, operands = res
    no_grad = tuple(None for _ in operands)
    if interpret is not None:
        heads = g.shape[2]
        delta = _row_delta(out, g.reshape(out.shape), heads)
        kv_heads = k.shape[-1] * heads // q.shape[-1]
        q, k, v = (a.reshape(*g.shape[:2], n, -1)
                   for a, n in ((q, heads), (k, kv_heads), (v, kv_heads)))
        # what the kernels read from heads-first copies goes behind a
        # barrier, or XLA shares the forward's copies with the backward's
        # and the padded copies live from one pass to the other
        chunk = _chunk_heads(heads, kv_heads, q.shape[-1], v.shape[-1], g.shape[1])
        if chunk == 1 and not _in_place(q.shape[-1]):
            q, k = lax.optimization_barrier((q, k))
        if chunk == 1 and not _in_place(v.shape[-1]):
            v = lax.optimization_barrier(v)
        return (*_flash_bwd(
            q, k, v, g.astype(q.dtype), lses, delta,
            causal, scale, block[1], kv_len, interpret, operands,
        ), no_grad)
    b, l_pad, h, d = q.shape
    n = l_pad // block
    do = g.astype(q.dtype)

    q_blocks = _to_blocks(q, n, block)
    k_blocks = _to_blocks(k, n, block)
    v_blocks = _to_blocks(v, n, block)
    do_blocks = _to_blocks(do, n, block)
    # delta_i = rowsum(dO . O) — the softmax-normalization term of dS
    delta_blocks = jnp.einsum(
        "nbqhd,nbqhd->nbhq",
        _to_blocks(out, n, block).astype(jnp.float32),
        _to_blocks(g, n, block).astype(jnp.float32),
    )  # (n, B, H, blk)
    block_pos = jnp.arange(block)
    idx = jnp.arange(n)
    live = _tile_live(causal, n, block, kv_len)

    # Pass 1: dQ.  Outer scan over Q blocks (ys only), inner scan over
    # K/V blocks with a (B, blk, H, D) f32 accumulator.
    def dq_body(q_blk, do_blk, lse_blk, delta_blk, q_idx, rows):
        q_pos = q_idx * block + block_pos

        def inner(dq, xs):
            k_blk, v_blk, k_idx, blocks = xs

            def update(dq):
                _, ds = _tile_grads(
                    q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                    q_pos, k_idx * block + block_pos, causal, scale, kv_len, blocks,
                )
                return dq + jnp.einsum(
                    "bhqk,bkhd->bqhd", ds.astype(k_blk.dtype), k_blk,
                    preferred_element_type=jnp.float32,
                )

            dq = _causal_skip(live(q_idx, k_idx), update, dq)
            return dq, None

        dq0 = jnp.zeros((b, block, h, d), jnp.float32)
        dq, _ = lax.scan(inner, dq0, (k_blocks, v_blocks, idx,
                                      tuple(_key_columns(a, n, block) for a in rows)))
        return dq

    _, dq_blocks = lax.scan(
        lambda _, xs: (None, dq_body(*xs)), None,
        (q_blocks, do_blocks, lses, delta_blocks, idx,
         tuple(_query_rows(a, n, block) for a in operands)),
    )

    # Pass 2: dK/dV.  Outer scan over K/V blocks, inner over Q blocks.
    def dkv_body(k_blk, v_blk, k_idx, columns):
        k_pos = k_idx * block + block_pos

        def inner(carry, xs):
            q_blk, do_blk, lse_blk, delta_blk, q_idx, blocks = xs

            def update(c):
                dk, dv = c
                p, ds = _tile_grads(
                    q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                    q_idx * block + block_pos, k_pos, causal, scale, kv_len, blocks,
                )
                dv = dv + jnp.einsum(
                    "bhqk,bqhd->bkhd", p.astype(do_blk.dtype), do_blk,
                    preferred_element_type=jnp.float32,
                )
                dk = dk + jnp.einsum(
                    "bhqk,bqhd->bkhd", ds.astype(q_blk.dtype), q_blk,
                    preferred_element_type=jnp.float32,
                )
                return dk, dv

            carry = _causal_skip(live(q_idx, k_idx), update, carry)
            return carry, None

        zero_k = jnp.zeros((b, block, h, d), jnp.float32)
        zero_v = jnp.zeros((b, block, h, v.shape[-1]), jnp.float32)
        (dk, dv), _ = lax.scan(
            inner, (zero_k, zero_v),
            (q_blocks, do_blocks, lses, delta_blocks, idx,
             tuple(_query_rows(a, n, block) for a in columns)),
        )
        return dk, dv

    _, (dk_blocks, dv_blocks) = lax.scan(
        lambda _, xs: (None, dkv_body(*xs)), None,
        (k_blocks, v_blocks, idx, tuple(_key_columns(a, n, block) for a in operands))
    )

    dq = _from_blocks(dq_blocks).astype(q.dtype)
    dk = _from_blocks(dk_blocks).astype(k.dtype)
    dv = _from_blocks(dv_blocks).astype(v.dtype)
    return dq, dk, dv, no_grad


_blockwise_padded.defvjp(_blockwise_padded_fwd, _blockwise_padded_bwd)


def _check_shapes(q, k, v):
    """q (B, L, H, D), k (B, L, Hkv, D), v (B, L, Hkv, Dv), ``Hkv``
    dividing ``H``."""
    same = (0, 1, 3)
    if (any(k.shape[i] != q.shape[i] for i in same) or v.shape[:3] != k.shape[:3]
            or q.shape[2] % k.shape[2]):
        raise ValueError(
            f"q/k/v shapes must match, got {q.shape}/{k.shape}/{v.shape}"
        )


def _padded_call(q, k, v, causal, block, scale, interpret, operands=()):
    l, d = q.shape[1], q.shape[-1]
    l_pad = pad_to(l, block if interpret is None else block[0])
    if l_pad != l:
        pad = [(0, 0), (0, l_pad - l), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # a rule that is causal on this row runs without its operands
    operands = (pad_operands(rule_operands(causal, operands), l, l_pad)
                if _is_rule(causal) else ())
    out = _blockwise_padded(q, k, v, operands, causal, block, l, float(scale), interpret)
    return out[:, :l]


def blockwise_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_size: int | None = None,
    scale: float | None = None,
    mask=None,
    mask_operands=(),
) -> jax.Array:
    """The scan schedule: what :func:`blockwise_attention` runs wherever
    its kernels do not, and what they are held to.  Grouped heads run as
    multi-head attention on copies of ``k`` and ``v``."""
    _check_shapes(q, k, v)
    k, v = _repeat_kv(q, k, v)
    block = min(_SCAN_BLOCK if block_size is None else block_size, q.shape[1])
    return _padded_call(q, k, v, mask_or_causal(causal, mask, q.shape[1]),
                        block, scale, None, mask_operands)


def engage_kernels(q, *, block_size: int | None = None,
                   interpret: bool | None = None,
                   shardable: bool = False,
                   v=None) -> bool | None:
    """Whether :func:`blockwise_attention` runs its flash kernels for
    queries shaped like ``q`` (B, L, H, D): the interpret flag they run
    with, or None for the scan schedule.  The op's shape rule (a head's
    dQ fits VMEM) and then `resolve_interpret`; the one predicate the
    op itself and ``attn_impl="auto"`` ask.  ``shardable``: the caller
    runs the op per shard under ``shard_map``.  ``v`` is the values
    (B, L, Hkv, Dv), which the op's own call knows: its verdict event
    says how many of the kernels' arrays stay in the model's rows
    (`layout_counts`); a caller that asks with ``q`` alone leaves the
    announcement of kernels that engage to the call that follows."""
    l, d = q.shape[1], q.shape[-1]
    l_pad = pad_to(l, _tiles(l, block_size)[0])
    if interpret is None and _bwd_vmem_bytes(l_pad, d, q.dtype) > _VMEM_BYTES:
        return None
    decision = resolve_interpret(interpret, shardable=shardable)
    if decision is None or v is not None:
        layout = None if v is None else dict(zip(
            ("operands_in_place", "operands_copied"),
            layout_counts(q.shape[2], v.shape[2], d, v.shape[3])))
        resolve_interpret(
            interpret, shardable=shardable, op="blockwise_attention",
            shape_class=shape_class(l=l, d=d), engaged_attrs=layout)
    return decision


def tile_counts(mask, length: int, *,
                block_size: int | None = None, kernels: bool = True
                ) -> tuple[float, float]:
    """(visited, needed) for one head of one row of ``length`` positions
    under the rule ``mask`` (one that is no plain causal there), forward
    and backward together, both in tiles of the backward's side: the
    tiles the sweeps visit, and the area of the scores that count.
    ``kernels``: the flash kernels' two sweeps in their own `_tiles`;
    else the scan schedule's three (forward, dQ, dK/dV) in its block."""
    if kernels:
        sides = _tiles(length, block_size)
        sweeps = (sides[0], 1), (sides[1], 1)
    else:
        sides = (min(_SCAN_BLOCK if block_size is None else block_size, length),) * 2
        sweeps = ((sides[0], 3),)
    l_pad, unit = pad_to(length, sides[0]), sides[1] ** 2
    visited = sum(
        times * int((_tile_classes(mask, l_pad, side, length) > 0).sum()) * side * side
        for side, times in sweeps)
    return visited / unit, sum(t for _, t in sweeps) * mask.area(length) / unit


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_size: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
    mask=None,
    mask_operands=(),
) -> jax.Array:
    """Exact attention over (B, L, H, D) without materializing (.., L, L).

    ``scale`` replaces the default ``1/sqrt(D)``; ``v`` may have a width
    of its own (latent attention: 192-wide queries and keys, 128-wide
    values), which the output takes.  ``mask``, a rule on positions
    (`ring_attention`'s protocol), stands in ``causal``'s place, with
    ``mask_operands`` the arrays it reads where it reads any (one byte a
    (query, key) pair, never a head's own); ``k`` and ``v`` may hold one
    head a group of query heads.

    ``block_size`` None: the scan schedule takes ``_SCAN_BLOCK`` (512),
    the kernels tiles that follow L alone (`_tiles`).  An explicit value
    is the schedule's block and every kernel tile's side (rounded up to
    whole lanes, 1024 at most).

    ``interpret``: None = auto (the kernels on a single-device TPU
    process or inside a manual region if a head's dQ fits VMEM, the
    scan schedule elsewhere); True runs the kernels in Pallas interpret
    mode on any backend.
    """
    _check_shapes(q, k, v)
    interpret = engage_kernels(q, block_size=block_size, interpret=interpret, v=v)
    if interpret is None:
        return blockwise_attention_reference(
            q, k, v, causal=causal, block_size=block_size, scale=scale, mask=mask,
            mask_operands=mask_operands)
    return _padded_call(
        q, k, v, mask_or_causal(causal, mask, q.shape[1]),
        _tiles(q.shape[1], block_size), scale, interpret, mask_operands)
