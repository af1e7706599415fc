"""Ring attention: exact attention over sequence shards on a ring (SP/CP).

Long-context sequence/context parallelism for tpuframe (absent from the
vision-only reference — SURVEY.md §5 — but first-class here): each device
holds a sequence shard of Q/K/V; K/V blocks rotate around the ``seq`` mesh
axis with ``jax.lax.ppermute`` (nearest-neighbour ICI hops) while every
device accumulates its queries' attention with an online-softmax, so the
full (L, L) score matrix never materializes and memory stays O(L/N * L/N)
per step.  Results are exact — identical to full attention — for both
causal and bidirectional masks.

Layout: per-device shards (batch, seq_local, heads, head_dim); the global
sequence is the concatenation of shards in ``seq``-axis index order.

Two entry points:
- :func:`ring_attention_local` — the per-device body; call it inside an
  existing ``shard_map`` (how the transformer blocks use it).
- :func:`ring_attention` — convenience wrapper that builds the shard_map
  over a mesh for standalone use/tests.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS


# -- masks that are rules on positions ------------------------------------------
#
# A mask that is neither causal nor full is a *rule*: a hashable value of
# a few static integers, never an array in HBM.  The oracle, the scan
# schedule and the flash kernels of `blockwise_attention` ask it, and
# name no class.  What a rule answers:
#
# - ``allowed(q_pos, k_pos, kv_len=None)``: which scores count, for int32
#   positions that broadcast against each other (a few compares, each
#   side coded along its own axis first: it runs on every masked tile of
#   a kernel).  With ``kv_len`` keys at or past it never count, and rows
#   at or past it (padding up to a tile) still see a key, so that every
#   logsumexp a kernel keeps is finite.
# - ``tiles(q_lo, q_hi, k_lo, k_hi)`` -> ``(live, whole)`` for tiles of
#   queries and keys (numpy ints, inclusive, broadcast): ``live`` holds a
#   score that counts, ``whole`` holds no other.  A sweep visits the live
#   tiles alone and masks element-wise those that are not whole.
# - ``area(length)``: the scores that count in one row of ``length``
#   positions, what a roofline and the ``attention/tiles_*`` counters
#   weigh the tiles visited against.
# - ``fits(length)``: whether it is a rule for a row of ``length``
#   positions; a rule says itself which rows it fits.
# - ``plain(length)``: True where the rule, on a row of ``length``
#   positions, is the causal mask itself; the op then runs as ``causal``
#   runs (no plan, no rule: `mask_or_causal`, the one place that asks).
# - ``suffix``: what the flash kernels' names carry under this rule in a
#   trace (``tpuframe_flash_fwd`` + suffix), so that a model's layers
#   under different rules can be told apart; empty keeps the plain names.
#
# A rule may also *read operands*: which scores count is then something
# the model computes, and no rule on positions can say it.  The rule
# itself stays the hashable value (what it answers statically it answers
# as above, judging a tile by what every operand could leave in it); the
# arrays travel beside it, ``mask_operands=``, through every schedule:
#
# - ``reads``: how many operands, each (B, L, L) with [row, query, key]
#   (0: a rule on positions alone).  A byte a (query, key) pair at most,
#   shared by all heads; padding up to a tile is the schedules' (`pad_operands`).
# - ``allowed(q_pos, k_pos, kv_len, *blocks)``: ``blocks`` are the
#   operands' values at those positions, [query, key] as the positions
#   broadcast (a leading batch axis outside a kernel, where a tile is one
#   row's).


def mask_or_causal(causal: bool, mask, length: int):
    """What stands in ``causal``'s place for a row of ``length``
    positions: the rule ``mask``, ``True`` where the rule is the causal
    mask there, or ``causal`` itself without a rule."""
    if mask is None:
        return bool(causal)
    if not mask.fits(length):
        raise ValueError(f"{mask} is no rule for a row of {length} positions")
    return True if mask.plain(length) else mask


class BlockDiffusionMask(NamedTuple):
    """The mask of a block-diffusion training row, as a rule on positions.

    The row is the noised copy of a sequence, positions ``[0, half)``,
    then its clean copy, ``[half, 2 * half)``, both in blocks of
    ``block`` positions.  With ``blk(p)`` a position's block within its
    copy, query ``i`` sees key ``j`` iff

    - both are noised and ``blk(i) == blk(j)`` (the whole block), or
    - ``i`` is noised, ``j`` clean and ``blk(j) < blk(i)``, or
    - both are clean and ``blk(j) <= blk(i)``;

    a clean query sees no noised key.  Positions past the row (padding
    up to a tile) count as clean blocks after the last, so every query
    has a key.  Two static integers, never an array in HBM: the oracle
    builds the dense mask from `allowed`, the kernels mask a tile
    element-wise with it, and `tiles` says which tiles to visit at all.
    """

    half: int
    block: int

    def _split(self, pos):
        """(noised, block within its copy) of int32 positions."""
        if self.half % self.block:
            raise ValueError(f"blocks of {self.block} do not divide {self.half} positions")
        noised = pos < self.half
        shift = self.block.bit_length() - 1
        if self.block == 1 << shift:  # no vector division in a kernel
            blk = lax.shift_right_logical(pos, jnp.full_like(pos, shift))
        else:
            blk = lax.div(pos, jnp.full_like(pos, self.block))
        return noised, jnp.where(noised, blk, blk - self.half // self.block)

    def allowed(self, q_pos, k_pos, kv_len=None):
        """Which scores count, for int32 positions that broadcast against
        each other; keys at or past ``kv_len`` never do.  Two compares
        and an ``or`` over the broadcast shape: each side is first coded
        along its own axis (a noised query's clean keys end one block
        before its own, and its noised keys are its own block's)."""
        q_noised, q_blk = self._split(jnp.asarray(q_pos, jnp.int32))
        k_pos = jnp.asarray(k_pos, jnp.int32)
        k_noised, k_blk = self._split(k_pos)
        k_clean = jnp.where(k_noised, jnp.iinfo(jnp.int32).max, k_blk)
        k_same = jnp.where(k_noised, k_blk, -1)     # -1: no noised key
        if kv_len is not None:
            k_clean = jnp.where(k_pos < kv_len, k_clean, jnp.iinfo(jnp.int32).max)
            k_same = jnp.where(k_pos < kv_len, k_same, -1)
        q_clean_to = jnp.where(q_noised, q_blk - 1, q_blk)  # last clean block seen
        q_same = jnp.where(q_noised, q_blk, -2)     # -2: a clean query matches none
        return (k_clean <= q_clean_to) | (k_same == q_same)

    def tiles(self, q_lo, q_hi, k_lo, k_hi):
        """(live, whole) for tiles of queries ``[q_lo, q_hi]`` and keys
        ``[k_lo, k_hi]`` (numpy ints, inclusive, broadcast): ``live``
        holds a score that counts, ``whole`` holds no other.  A tile is
        cut at ``half`` into its four quadrant parts, each judged by the
        blocks its two ranges span."""
        half, b = self.half, self.block
        qn, qc = q_lo < half, q_hi >= half
        kn, kc = k_lo < half, k_hi >= half
        a0, a1 = q_lo // b, np.minimum(q_hi, half - 1) // b        # noised queries
        c0, c1 = k_lo // b, np.minimum(k_hi, half - 1) // b        # noised keys
        e0, e1 = (np.maximum(q_lo, half) - half) // b, (q_hi - half) // b
        d0, d1 = (np.maximum(k_lo, half) - half) // b, (k_hi - half) // b
        nn, nc, cc = qn & kn, qn & kc, qc & kc
        live = ((nn & (a0 <= c1) & (c0 <= a1)) | (nc & (d0 < a1))
                | (cc & (d0 <= e1)))
        whole = (~(qc & kn)
                 & (~nn | ((a0 == a1) & (c0 == c1) & (a0 == c0)))
                 & (~nc | (d1 < a0)) & (~cc | (d1 <= e0)))
        return live, live & whole

    def area(self, length: int | None = None) -> int:
        """Scores that count in one row: ``half^2 + half * block``
        (``length`` is the row's own, ``2 * half``)."""
        return self.half * (self.half + self.block)

    def fits(self, length: int) -> bool:
        return length == 2 * self.half

    def plain(self, length: int) -> bool:
        return False

    suffix = ""


class SlidingWindowMask(NamedTuple):
    """A causal band: query ``i`` sees key ``j`` iff ``i - window < j <= i``,
    the ``window`` keys up to and with its own (the Hugging Face sliding
    window).  A rule for a row of any length; on a row no longer than
    the window it is the causal mask."""

    window: int

    def allowed(self, q_pos, k_pos, kv_len=None):
        """Two compares over the broadcast shape; a row at or past
        ``kv_len`` sees what the last row before it sees."""
        q_pos = jnp.asarray(q_pos, jnp.int32)
        k_pos = jnp.asarray(k_pos, jnp.int32)
        if kv_len is not None:
            q_pos = jnp.minimum(q_pos, kv_len - 1)
        return (k_pos <= q_pos) & (k_pos > q_pos - self.window)

    def tiles(self, q_lo, q_hi, k_lo, k_hi):
        """A tile meets the band iff its first key is not after its last
        query and its last key is inside the first query's window; it
        lies inside the band iff its last key is not after its first
        query and its first key is inside the last query's window."""
        live = (k_lo <= q_hi) & (k_hi > q_lo - self.window)
        whole = (k_hi <= q_lo) & (k_lo > q_hi - self.window)
        return live, live & whole

    def area(self, length: int) -> int:
        """``length * window - window (window - 1) / 2`` from ``window``
        positions on; the causal triangle below."""
        w = min(self.window, length)
        return length * w - w * (w - 1) // 2

    def fits(self, length: int) -> bool:
        return self.window >= 1

    def plain(self, length: int) -> bool:
        return self.window >= length

    suffix = "_window"


class SelectedKeysMask(NamedTuple):
    """Attention over keys the model chose: query ``i`` sees key ``j`` iff
    the operand ``chosen`` (B, L, L) int8 holds a 1 at [i, j]
    (`ops.sparse_index.select_keys` makes it: the ``topk`` keys not after
    the query that its index ranks highest, every key not after it where
    there are no more than ``topk``).  What is static: a row no longer
    than ``topk`` is causal; a tile whose last query lies under ``topk``
    is judged as causal judges it; every other tile not above the diagonal
    may hold a chosen key and is masked element-wise by the operand."""

    topk: int
    #: a NamedTuple compares as its tuple: without this a band of as many
    #: keys would be the same static argument to every cache that keys on a rule
    kind: str = "selected"

    reads = 1

    def allowed(self, q_pos, k_pos, kv_len=None, chosen=None):
        """The operand's block at these positions says it all: causality,
        keys past ``kv_len`` and padded rows are `pad_operands`'s."""
        return chosen != 0

    def tiles(self, q_lo, q_hi, k_lo, k_hi):
        live = k_lo <= q_hi
        return live, live & (k_hi <= q_lo) & (q_hi < self.topk)

    def area(self, length: int) -> int:
        """``sum over t of min(t + 1, topk)``."""
        k = min(self.topk, length)
        return k * (k + 1) // 2 + (length - k) * k

    def fits(self, length: int) -> bool:
        return self.topk >= 1

    def plain(self, length: int) -> bool:
        return length <= self.topk

    suffix = "_select"


def rule_operands(mask, operands) -> tuple:
    """``operands`` as the tuple the rule ``mask`` reads (none for a rule on
    positions, for causal and for no mask)."""
    operands = tuple(operands or ())
    reads = getattr(mask, "reads", 0)
    if len(operands) != reads:
        raise ValueError(f"{mask} reads {reads} operand(s), got {len(operands)}")
    return operands


def pad_operands(operands, length: int, l_pad: int) -> tuple:
    """A rule's operands (B, length, length) for a row padded to ``l_pad``
    positions: no key past the row counts, and a row past it sees key 0, so
    that every logsumexp a schedule keeps is finite."""
    if l_pad == length:
        return tuple(operands)
    grow = [(0, 0), (0, l_pad - length), (0, l_pad - length)]
    return tuple(jnp.pad(a, grow).at[:, length:, 0].set(1) for a in operands)


def _repeat_kv(q, k, v):
    """Grouped heads as multi-head attention: each key/value head copied
    to the query heads of its group (query head ``j`` uses ``j // group``)."""
    group, rest = divmod(q.shape[2], k.shape[2])
    if rest or v.shape[2] != k.shape[2]:
        raise ValueError(
            f"{k.shape[2]}/{v.shape[2]} key/value heads do not group {q.shape[2]} query heads")
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    scale: float | None = None, mask=None, mask_operands=(),
) -> jax.Array:
    """Full (unsharded) attention oracle, (B, L, H, D) layout.

    ``scale`` replaces the default ``1/sqrt(D)`` (latent attention folds
    its rotary scaling into it); ``v`` may be narrower or wider than
    ``q``/``k``: the output takes ``v``'s width.  ``mask`` is a rule on
    positions (the protocol above) in place of ``causal`` (the dense
    mask is built from it here, with ``mask_operands`` where the rule
    reads any); ``k`` and ``v`` may hold fewer heads than ``q``, one a
    group."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    k, v = _repeat_kv(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = mask_or_causal(causal, mask, q.shape[1])
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        blocks = () if causal is True else rule_operands(mask, mask_operands)
        if blocks:
            seen = causal.allowed(qi, ki, None, *blocks)[:, None]
        else:
            seen = ki <= qi if causal is True else causal.allowed(qi, ki)
        scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _seen(causal, q_pos, k_pos, blocks=()):
    """(Lq, Lk) bool: ``causal`` True, or a rule on positions in its place;
    (B, Lq, Lk) under a rule that reads operands, ``blocks`` their values
    at these positions."""
    if causal is True:
        return k_pos[None, :] <= q_pos[:, None]
    if blocks:
        return causal.allowed(q_pos[:, None], k_pos[None, :], None, *blocks)
    return causal.allowed(q_pos[:, None], k_pos[None, :])


def _over_heads(seen):
    """A mask (Lq, Lk) or (B, Lq, Lk) against scores (B, H, Lq, Lk)."""
    return seen[None, None] if seen.ndim == 2 else seen[:, None]


def _block_update(q, k, v, o, l, m, q_pos, k_pos, causal, scale,
                  kv_len: int | None = None, blocks=()):
    """Online-softmax accumulation of one K/V block into (o, l, m).

    ``kv_len`` masks padded key positions (``k_pos >= kv_len``) — used by
    the blockwise schedule, which pads the sequence to a block multiple.
    ``blocks``: what a rule that reads operands reads at this tile.

    q/k/v keep their storage dtype: the MXU multiplies bf16 natively and
    accumulates f32 (``preferred_element_type``), so upcasting the
    operands first would only drop matmul throughput ~4x (measured on
    v5e: the f32-upcast version ran the seq-8192 blockwise step at MFU
    0.042).  All softmax state (o, l, m) stays f32.
    """
    s = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )  # (B, H, Lq, Lk) f32
    if causal:
        mask = _seen(causal, q_pos, k_pos, blocks)  # (Lq, Lk)
        s = jnp.where(_over_heads(mask), s, -jnp.inf)
    if kv_len is not None:
        s = jnp.where((k_pos < kv_len)[None, None, None, :], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (B, H, Lq)
    # exp(-inf - m) -> 0 handles fully-masked rows; keep m finite
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])  # (B, H, Lq, Lk)
    # When the prior running max m is -inf (first block, or fully-masked so
    # far) the correct correction is 0, not exp(m_new): o and l are still 0,
    # and exp(m_new) overflows to inf for large logits, turning 0*inf → NaN.
    correction = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_new))
    correction = jnp.where(jnp.isneginf(m_new), 0.0, correction)
    l_new = l * correction + jnp.sum(p, axis=-1)
    # probabilities in the value dtype for the second MXU matmul (the
    # standard flash recipe), f32 accumulation into o
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, l_new, m_new


def _tile_grads(q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                q_pos, k_pos, causal, scale, kv_len=None, blocks=()):
    """(p, ds) for one (Q block, K/V block) tile of the flash backward.

    Probabilities are recomputed from the saved logsumexp —
    ``p = exp(s - lse)`` — so nothing O(L^2) is ever stored.  Fully
    masked rows have ``lse = -inf``; masking s to -inf first makes
    ``exp`` produce exact zeros for them.  Shared by the blockwise
    (single-device) and ring (sequence-parallel) backward passes.
    """
    s = (
        jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                   preferred_element_type=jnp.float32)
        * scale
    )
    valid = None
    if kv_len is not None:
        valid = (k_pos < kv_len)[None, :]
    if causal:
        cmask = _seen(causal, q_pos, k_pos, blocks)
        valid = cmask if valid is None else (valid & cmask)
    if valid is not None:
        s = jnp.where(_over_heads(valid), s, -jnp.inf)
    lse_safe = jnp.where(jnp.isneginf(lse_blk), 0.0, lse_blk)
    p = jnp.exp(s - lse_safe[..., None])  # (B, H, bq, bk) f32, exact rows
    dp = jnp.einsum("bqhd,bkhd->bhqk", do_blk, v_blk,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta_blk[..., None]) * scale
    return p, ds


def _causal_skip(pred, update, carry):
    """Apply ``update(carry)``, branch-skipped when ``pred`` is given.

    The causal tile skip shared by every blockwise/ring sweep: ``pred``
    is None for bidirectional attention (always update) or a scalar
    "tile intersects the causal triangle" predicate — scalar ``lax.cond``
    lowers to a real XLA Conditional inside scan/shard_map bodies, so
    skipped tiles execute nothing.  Collectives must stay OUTSIDE the
    cond (every device has to participate).
    """
    if pred is None:
        return update(carry)
    return lax.cond(pred, update, lambda c: c, carry)


def _ring_fwd_loop(q, k, v, axis_name, causal):
    """The rotating online-softmax sweep -> (out, lse)."""
    axis_size = jax.lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    lk = k.shape[1]

    q_pos = my_idx * lq + jnp.arange(lq)
    o = jnp.zeros((b, lq, h, d), jnp.float32)
    l = jnp.zeros((b, h, lq), jnp.float32)
    m = jnp.full((b, h, lq), -jnp.inf, jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for step in range(axis_size):
        # after `step` hops, this device holds the block that started at
        # ring position (my_idx - step)
        src = (my_idx - step) % axis_size
        k_pos = src * lk + jnp.arange(lk)

        def update(c, k=k, v=v, k_pos=k_pos):
            return _block_update(q, k, v, *c, q_pos, k_pos, causal, scale)

        # a visiting block strictly above the diagonal contributes nothing
        o, l, m = _causal_skip(
            (src <= my_idx) if causal else None, update, (o, l, m)
        )
        if step + 1 < axis_size:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (strict causal pad) -> 0
    lse = m + jnp.log(l)  # -inf rows stay -inf (m dominates)
    out = (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_fused(q, k, v, axis_name, causal):
    out, _ = _ring_fused_fwd(q, k, v, axis_name, causal)
    return out


def _ring_fused_fwd(q, k, v, axis_name, causal):
    out, lse = _ring_fwd_loop(q, k, v, axis_name, causal)
    return out, (q, k, v, out, lse)


def _ring_fused_bwd(axis_name, causal, res, g):
    """Flash-style ring backward: one more sweep around the ring.

    Reverse-mode through the unrolled forward saved every hop's
    residuals (O(ring_size) big tensors per device) and re-ran the
    sweep; instead this recomputes each tile from the saved O(L)
    logsumexp.  dK/dV accumulators TRAVEL WITH their K/V blocks: each
    hop computes the visiting block's tile gradients locally, adds into
    the accumulators riding alongside, and rotates all four buffers
    together — after ``axis_size`` rotations every dK/dV lands back on
    its home device.  dQ accumulates locally.
    """
    q, k, v, out, lse = res
    axis_size = jax.lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    lk = k.shape[1]
    do = g.astype(q.dtype)
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", out.astype(jnp.float32), g.astype(jnp.float32)
    )
    q_pos = my_idx * lq + jnp.arange(lq)

    dq = jnp.zeros((b, lq, h, d), jnp.float32)
    dk = jnp.zeros((b, lk, h, d), jnp.float32)
    dv = jnp.zeros((b, lk, h, d), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for step in range(axis_size):
        src = (my_idx - step) % axis_size
        k_pos = src * lk + jnp.arange(lk)

        def update(c, k=k, v=v, k_pos=k_pos):
            dq, dk, dv = c
            p, ds = _tile_grads(q, k, v, do, lse, delta, q_pos, k_pos,
                                causal, scale)
            dq = dq + jnp.einsum(
                "bhqk,bkhd->bqhd", ds.astype(k.dtype), k,
                preferred_element_type=jnp.float32,
            )
            dk = dk + jnp.einsum(
                "bhqk,bqhd->bkhd", ds.astype(q.dtype), q,
                preferred_element_type=jnp.float32,
            )
            dv = dv + jnp.einsum(
                "bhqk,bqhd->bkhd", p.astype(do.dtype), do,
                preferred_element_type=jnp.float32,
            )
            return dq, dk, dv

        dq, dk, dv = _causal_skip(
            (src <= my_idx) if causal else None, update, (dq, dk, dv)
        )
        # rotate k/v with their gradient accumulators; k/v are dead
        # after the last compute (as in the forward) but dk/dv need the
        # final hop to land back on their home device
        if step + 1 < axis_size:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)

    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_fused.defvjp(_ring_fused_fwd, _ring_fused_bwd)


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = False,
) -> jax.Array:
    """Per-device ring attention body (call under shard_map).

    Args are this device's shards, (B, L_local, H, D).  K/V travel the
    ring ``axis_size`` times; the python loop is a static unroll (the
    ring size is a mesh constant), which lets XLA overlap each hop's
    ppermute with the previous block's compute.  Differentiation uses
    the hand-written flash-style backward (`_ring_fused_bwd`) rather
    than reverse-mode through the unrolled loop.
    """
    return _ring_fused(q, k, v, axis_name, causal)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    causal: bool = False,
    seq_axis: str = SEQUENCE_AXIS,
    batch_axes=(DATA_AXIS, FSDP_AXIS),
    head_axis: str | None = None,
) -> jax.Array:
    """shard_map wrapper: global (B, L, H, D) arrays over ``mesh``.

    Batch splits over ``batch_axes``, sequence over ``seq_axis``, heads
    over ``head_axis`` (tensor parallel) when given.
    """
    spec = P(tuple(batch_axes), seq_axis, head_axis, None)
    fn = functools.partial(ring_attention_local, axis_name=seq_axis, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
