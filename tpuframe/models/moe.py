"""Mixture-of-Experts MLP with expert parallelism over the ``expert`` axis.

Absent from the vision-only reference (SURVEY.md §2.2 marks EP "No"), but
the ``expert`` mesh axis is first-class in tpuframe.  TPU-first design —
the GShard/Switch dense-dispatch formulation: routing becomes einsums
against one-hot dispatch/combine tensors (MXU work, static shapes), and
expert parallelism is *declared* by sharding the expert-stacked weights
``(E, ...)`` over the ``expert`` axis — GSPMD inserts the all-to-alls
that imperative MoE frameworks hand-write.

Components:
- :class:`MoEMLP` — drop-in replacement for a transformer block's MLP:
  top-k softmax gating and a balance loss exposed via the ``"aux_loss"``
  mutable collection; either capacity-factor truncation (GShard dense
  dispatch) or, with ``capacity_factor=None``, no token dropped: the
  assignments in slots ordered by expert through ``ops.grouped_matmul``
  in buffers bounded to the rows routed here, the experts ``held`` here
  out of all the router scores, and a shared MLP beside them.  Where the
  buffers are shorter than the (token, choice) pairs the slots' plan is
  made by counting, for the buffers' slots alone; a layer that holds a
  slot a pair sorts the pairs.
- :func:`moe_rules` — ParallelPlan rules placing expert weights on the
  ``expert`` axis (compose with the TP/fsdp rules).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import EXPERT_AXIS
from tpuframe.ops.grouped_matmul import (
    grouped_matmul,
    grouped_matmul_grads,
    row_tile,
    tiles_visited,
)
from tpuframe.ops.moe_gating import moe_dispatch_combine
from tpuframe.ops.unsort import unsort


def moe_rules():
    """ParallelPlan rules: expert-stacked weights shard over ``expert``.
    The router's kernel and the selection bias (``expert_bias``) score
    every expert for every token and stay whole: no rule names them."""
    return (
        (r"(^|/)(w_gate|w_in|w_out)$", P(EXPERT_AXIS, None, None)),
    )


#: pairs a block of the counting route: a block's keys are one row of lanes,
#: and a count inside a block is at most what a bfloat16 holds exactly
_BLOCK = 256
#: the slot of a pair routed to no held expert: outside every window
_NOWHERE = 1 << 30


class _Counted(NamedTuple):
    """The counting route's tables (:func:`_count_blocks`) and the window's
    first slot: what a pair's slot is read from where XLA's form of the
    un-sort asks for it (:func:`_pair_slots`); the kernels never do."""

    key: jax.Array    # (P,) a pair's key: its held expert, or `count`
    edges: jax.Array  # (count * nb,) slots up to the end of (expert, block)
    lo: jax.Array     # the window's first slot


def _blocks_of(pairs: int) -> int:
    return -(-pairs // _BLOCK)


def _in_blocks(flat, fill):
    """(P,) -> (nb, _BLOCK): the pairs in blocks, the last filled up."""
    short = -flat.shape[0] % _BLOCK
    if short:
        flat = jnp.pad(flat, (0, short), constant_values=fill)
    return flat.reshape(-1, _BLOCK)


def _count_blocks(key, count: int):
    """``key`` (P,), a pair's held expert or ``count`` -> ``(sizes, edges)``:
    the pairs of each held expert (count,), and for every (expert, block of
    ``_BLOCK`` pairs), expert-major, the sorted slots up to the end of that
    block's pairs of that expert (count * nb,): ascending, so a slot's
    expert and block are one count of compares.  Compares and sums of 0/1
    numbers over whole arrays; nothing is sorted or scattered."""
    held = lax.iota(jnp.int32, count)[:, None, None]
    per_block = jnp.sum((_in_blocks(key, count)[None] == held).astype(jnp.int32), axis=2)
    return jnp.sum(per_block, axis=1), jnp.cumsum(per_block.reshape(-1))  # per_block: (count, nb)


def _byte_planes(x, planes: int):
    """The low ``planes`` bytes of ``x``'s bits, each as bfloat16 numbers
    0..255 along a new leading axis: what a one-hot product moves exactly,
    whatever the bits spell (a NaN, an infinity, a number under the
    normal range)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32) if x.dtype != jnp.uint32 else x
    shifts = (8 * lax.iota(jnp.uint32, planes)).reshape((planes,) + (1,) * x.ndim)
    return ((bits[None] >> shifts) & 0xFF).astype(jnp.bfloat16)


def _from_planes(planes):
    """uint32 bits from byte planes along the leading axis (exact whole
    numbers 0..255 in any float dtype)."""
    shifts = (8 * lax.iota(jnp.uint32, planes.shape[0])).reshape((-1,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.uint32) << shifts, axis=0)


def _window_plan(key, edges, vals, lo, cap: int, k: int):
    """The plan of the window ``[lo, lo + cap)`` of the slots, by counting:
    ``tok`` (cap,) the token of the pair in each slot, ``weight`` (cap,) its
    gate value out of ``vals`` (P,), ``pair`` (cap,) its number ``t k + j``
    (-1 past the live slots, where ``tok`` and ``weight`` are 0).  Slots go
    by expert and, inside an expert, by pair number: what a stable sort of
    the keys gives, element for element.

    A slot's (expert, block) is the count of ``edges`` at or under it; its
    block's keys and gate values come as rows out of the (nb, _BLOCK) tables
    by a one-hot product on byte planes (exact: one term a row); the pair is
    the lane whose running count of the expert's keys reaches the slot's
    rank in the block (a product with a triangle of ones).  The gate value
    is selected, never multiplied: a non-finite one stays with its pair."""
    nb, bf16 = _blocks_of(key.shape[0]), jnp.bfloat16
    count = edges.shape[0] // nb
    key_planes = 1 if count < 256 else 2
    slot = lo + lax.iota(jnp.int32, cap)
    under = edges[None, :] <= slot[:, None]  # (cap, count * nb), never stored
    at = jnp.sum(under.astype(jnp.int32), axis=1)
    rank = slot + 1 - jnp.max(jnp.where(under, edges[None, :], 0), axis=1)
    expert, block, live = at // nb, at % nb, slot < edges[-1]
    table = jnp.concatenate([_byte_planes(_in_blocks(key, count).astype(jnp.uint32), key_planes),
                             _byte_planes(_in_blocks(vals.astype(jnp.float32), 0), 4)])
    onehot = (block[:, None] == lax.iota(jnp.int32, nb)[None, :]).astype(bf16)
    picked = jnp.einsum("ab,pbl->pal", onehot, table, preferred_element_type=bf16)
    mine = (_from_planes(picked[:key_planes]).astype(jnp.int32) == expert[:, None])
    lanes = lax.iota(jnp.int32, _BLOCK)
    upto = (lanes[:, None] <= lanes[None, :]).astype(bf16)
    run = jnp.dot(mine.astype(bf16), upto, preferred_element_type=jnp.float32)
    hit = mine & (run == rank[:, None].astype(jnp.float32)) & live[:, None]
    lane = jnp.sum(jnp.where(hit, lanes[None, :], 0), axis=1)
    chosen = jnp.sum(jnp.where(hit[None], picked[key_planes:].astype(jnp.float32), 0.0), axis=2)
    weight = lax.bitcast_convert_type(_from_planes(chosen), jnp.float32)
    pair = jnp.where(live, block * _BLOCK + lane, -1)
    return jnp.where(live, pair // k, 0), weight, pair


def _spread_weights(d_weight, pair, pairs: int):
    """The transpose of the plan's selection: (pairs,) zeros with
    ``d_weight[a]`` at pair ``pair[a]``, each pair from at most one slot,
    so no sum rounds.  A one-hot product on byte planes again, (nb, cap) @
    (cap, 4 * _BLOCK): nothing is scattered."""
    nb = _blocks_of(pairs)
    # tied to the cotangent: what is made of `pair` alone (the one-hot, 2 MiB
    # a layer) XLA would else make in the forward pass and keep across the
    # step's peak, as it did `ops/unsort.py`'s `place` (PERF.md, PR 48)
    d_weight, pair = lax.optimization_barrier((d_weight, pair))
    block, lane = pair // _BLOCK, pair % _BLOCK  # a dead slot's block is -1: no row
    onehot = (lax.iota(jnp.int32, nb)[:, None] == block[None, :]).astype(jnp.bfloat16)
    here = lane[:, None] == lax.iota(jnp.int32, _BLOCK)[None, :]
    planes = jnp.where(here[None], _byte_planes(d_weight.astype(jnp.float32), 4)[:, :, None], 0)
    spread = jnp.einsum("ba,pal->pbl", onehot, planes, preferred_element_type=jnp.float32)
    out = lax.bitcast_convert_type(_from_planes(spread), jnp.float32)
    return out.reshape(-1)[:pairs]


def _pair_slots(route: _Counted):
    """(P,) every pair's slot counted from the window's first:
    the slots of its expert's pairs in the blocks before + its expert's
    pairs before it in its block; ``_NOWHERE`` for a pair routed to no
    held expert.  What ``argsort(argsort(key))`` gives on the held pairs.
    Made where XLA's form of the un-sort reads it and nowhere else."""
    key, edges, lo = route
    nb = _blocks_of(key.shape[0])
    count = edges.shape[0] // nb
    blocks = _in_blocks(key, count)
    before = jnp.concatenate([jnp.zeros((1,), edges.dtype), edges[:-1]]).reshape(count, nb)
    held = lax.iota(jnp.int32, count)[:, None, None]
    start = jnp.sum(jnp.where(blocks[None] == held, before[:, :, None], 0), axis=0)
    lanes = lax.iota(jnp.int32, _BLOCK)
    earlier = (blocks[:, :, None] == blocks[:, None, :]) & (lanes[None, :] < lanes[:, None])[None]
    slots = start + jnp.sum(earlier.astype(jnp.int32), axis=2)
    return jnp.where(blocks < count, slots - lo, _NOWHERE).reshape(-1)[:key.shape[0]]


def _sum_choices_impl(rows, inv, n):
    if isinstance(inv, _Counted):
        inv = _pair_slots(inv)
    if rows.shape[0] == inv.shape[0]:
        return rows[inv].reshape(n, -1, rows.shape[-1]).sum(axis=1)
    # a window of the slots: a slot outside it reads as zero.  Gathered
    # with the choices in front, (k, n, d): on the TPU an (n, 6, d) array
    # is re-tiled and costs twice as much (eight the same; PERF.md, PR 32)
    slots = inv.reshape(n, -1).T
    inside = (slots >= 0) & (slots < rows.shape[0])
    picked = jnp.where(inside[..., None], rows[jnp.where(inside, slots, 0)], 0)
    return picked.sum(axis=0, dtype=jnp.float32).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _take_tokens(tokens, tok, inv, sizes, n, kernels):
    """``rows[a] = tokens[tok[a]]`` for the slots ``tok`` names (all of
    them, or a window).  Its transpose is written as a gather too (un-sort
    by ``inv``, then sum each token's choices): the scatter-add autodiff
    would emit is the slow form on the TPU.  ``inv`` is every pair's slot,
    or the counting route's tables (:class:`_Counted`) it is read from
    where XLA's form of the un-sort asks."""
    return tokens[tok]


def _take_tokens_fwd(tokens, tok, inv, sizes, n, kernels):
    return tokens[tok], (tok, inv, sizes)


def _take_tokens_bwd(n, kernels, res, g):
    return _sum_choices(g, *res, n, kernels), None, None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


def _sum_choices_fn(rows, tok, inv, sizes, n, kernels):
    xla = functools.partial(_sum_choices_impl, rows, inv, n)
    if sizes is None or not kernels:
        return xla()
    return unsort(rows, tok, sizes, n, otherwise=xla)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sum_choices(rows, tok, inv, sizes, n, kernels):
    """``out[t] = sum of the rows assigned from token t``: the transpose
    of :func:`_take_tokens`.  ``rows`` may be a window of the slots (those
    ``tok`` names, ``inv`` counted from its first, ``sizes`` the groups'
    share of it, None where ``rows`` are all the slots); a slot outside it
    adds zero.  A window is ``ops.unsort``'s: its kernel reads the routed
    rows alone and, where it does not run (``kernels=False``, a CPU,
    several devices, a shape it refuses), :func:`_sum_choices_impl` is the
    program as it was: a gather a (token, choice) pair by ``inv``, which
    the kernel never reads."""
    return _sum_choices_fn(rows, tok, inv, sizes, n, kernels)


def _sum_choices_fwd(rows, tok, inv, sizes, n, kernels):
    return _sum_choices_fn(rows, tok, inv, sizes, n, kernels), (tok, inv, sizes)


def _sum_choices_bwd(n, kernels, res, g):
    return _take_tokens(g, *res, n, kernels), None, None, None


_sum_choices.defvjp(_sum_choices_fwd, _sum_choices_bwd)


#: the slot buffers' rows come in whole multiples of this (a constant of
#: the buffers: the grouped product's row tile is its kernels' own)
_SLOT_ROWS = 512


def slot_bound(pairs: int, count: int, experts: int) -> int:
    """Rows of the no-drop layer's slot buffers: twice the share of the
    ``pairs`` (token, choice) pairs that a balanced router sends to
    ``count`` held experts of ``experts``, rounded up to ``_SLOT_ROWS``,
    and never more than a slot a pair."""
    balanced = -(-pairs * count // experts)
    return min(pairs, -(-2 * balanced // _SLOT_ROWS) * _SLOT_ROWS)


def _scale_rows(y, weight):
    return y * weight[:, None].astype(y.dtype)


def _slots_mlp(tokens, w_gate, w_in, w_out, weight, tok, inv, sizes, window, act, kernels):
    """``sum p_e E_e(x)`` over the slots ``tok`` and ``weight`` name (all
    of them, or a window: ``window`` is then the groups' share of it, for
    the un-sort), and the arrays the backward pass reads."""
    n = tokens.shape[0]
    rows = _take_tokens(tokens, tok, inv, window, n, kernels)
    product = functools.partial(grouped_matmul, group_sizes=sizes, kernels=kernels)
    pre = product(rows, w_in)
    if w_gate is not None:
        gate = product(rows, w_gate)
        hid = act(gate) * pre
    else:
        gate, hid = None, act(pre)
    y = product(hid, w_out)
    out = _sum_choices(_scale_rows(y, weight), tok, inv, window, n, kernels)
    return out, (rows, gate, pre, hid, y, weight, tok, inv, sizes)


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _expert_parts(tokens, w_gate, w_in, w_out, weight, tok, inv, sizes, lo, cap, act,
                  kernels=True):
    """A layer that holds a slot a pair (``cap`` is the pairs): the sorted
    route's ``weight``, ``tok`` and ``inv`` whole.  ``lo`` and ``cap`` are
    read by nothing: they stay in the signature so that this layer's program
    is, byte for byte, the one it was when windows of a sorted route came
    through here too.  Jitted, like :func:`_counted_parts`: the layers of a
    model are then one traced and lowered function the step calls, not a
    copy each (seconds of a job's first step)."""
    return _slots_mlp(tokens, w_gate, w_in, w_out, weight, tok, inv, sizes, None, act, kernels)


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def _counted_parts(tokens, w_gate, w_in, w_out, vals, key, sizes, edges, lo, cap, k, act,
                   kernels=True):
    """The window ``[lo, lo + cap)`` of the slots, its plan made by
    counting (:func:`_window_plan`): the experts' sum over it, the arrays
    its backward pass reads and each slot's pair.  ``kernels=False`` (the
    further windows) keeps the grouped products on ``ragged_dot`` and the
    un-sort on XLA's form: a loop's body is lowered for itself, and every
    kernel in it is one more for each layer's executable to compile and
    load for traffic that overflows the buffers."""
    with jax.named_scope("tpuframe/moe/route"):
        ends = jnp.cumsum(sizes)
        window = jnp.clip(ends, lo, lo + cap) - jnp.clip(ends - sizes, lo, lo + cap)
        tok, weight, pair = _window_plan(key, edges, vals, lo, cap, k)
    out, parts = _slots_mlp(tokens, w_gate, w_in, w_out, weight, tok, _Counted(key, edges, lo),
                            window, window, act, kernels)
    return out, parts, pair


def _windows(sizes, cap):
    """Windows of ``cap`` slots that hold the pairs routed here."""
    return jnp.maximum(-(-jnp.sum(sizes) // cap), 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _experts_windowed(tokens, w_gate, w_in, w_out, vals, key, sizes, edges, cap, k, act):
    """The held experts' MLPs through buffers of ``cap`` slots: the
    window of the slots that holds every pair routed here unless the
    router sent more than ``cap``, and then window after window until all
    are done, each asking :func:`_counted_parts` for its own plan.  Nothing
    is dropped, no buffer has a slot a pair, and nothing as long as the
    pairs is sorted, gathered from or scattered into.

    A ``custom_vjp``: what the first window computed is saved for the
    backward pass, ``cap`` rows long; a further window saves nothing and
    computes its forward pass again there.  The gate values' cotangent
    (``vals``, a number a pair) comes from each window's ``cap`` slots by
    :func:`_spread_weights`."""
    return _experts_windowed_fwd(tokens, w_gate, w_in, w_out, vals, key, sizes, edges,
                                 cap, k, act)[0]


def _experts_windowed_fwd(tokens, w_gate, w_in, w_out, vals, key, sizes, edges, cap, k, act):
    args = (tokens, w_gate, w_in, w_out, vals, key, sizes, edges)
    out, *first = _counted_parts(*args, jnp.int32(0), cap, k, act)
    out = jax.lax.fori_loop(
        1, _windows(sizes, cap),
        lambda i, acc: acc + _counted_parts(*args, i * cap, cap, k, act, False)[0], out)
    return out, (first, args)


@functools.partial(jax.jit, static_argnames=("n", "act", "kernels"))
def _window_bwd(parts, pair, *, w_gate, w_in, w_out, n, act, g, kernels=True):
    """The transposes of :func:`_slots_mlp`'s lines, last to first, each
    from the arrays the forward pass made: cotangents of the tokens, the
    three weights and the window's gate weights."""
    rows, gate, pre, hid, y, weight, tok, inv, sizes = parts
    grads = functools.partial(grouped_matmul_grads, group_sizes=sizes, kernels=kernels)
    d_y, d_weight = jax.vjp(_scale_rows, y, weight)[1](
        _take_tokens(g, tok, inv, sizes, n, kernels))
    d_hid, d_out = grads(hid, w_out, g=d_y)
    if w_gate is not None:
        d_gate, d_pre = jax.vjp(lambda a, b: act(a) * b, gate, pre)[1](d_hid)
        d_rows, d_wg = grads(rows, w_gate, g=d_gate)
    else:
        (d_pre,), d_rows, d_wg = jax.vjp(act, pre)[1](d_hid), 0, None
    d_more, d_in = grads(rows, w_in, g=d_pre)
    d_tokens = _sum_choices(d_rows + d_more, tok, inv, sizes, n, kernels)
    with jax.named_scope("tpuframe/moe/route"):
        d_vals = _spread_weights(d_weight, pair, inv.key.shape[0])
    return d_tokens, d_wg, d_in, d_out, d_vals


def _experts_windowed_bwd(cap, k, act, res, g):
    first, args = res
    tokens, w_gate, w_in, w_out, vals, _, sizes, _ = args
    window = functools.partial(_window_bwd, w_gate=w_gate, w_in=w_in, w_out=w_out,
                               n=tokens.shape[0], act=act, g=g)

    def further(i, acc):
        again = _counted_parts(*args, i * cap, cap, k, act, False)[1:]
        return jax.tree.map(jnp.add, acc, window(*again, kernels=False))

    *d, d_vals = jax.lax.fori_loop(1, _windows(sizes, cap), further, window(*first))
    return (*d, d_vals.astype(vals.dtype), None, None, None)


_experts_windowed.defvjp(_experts_windowed_fwd, _experts_windowed_bwd)


class MoEMLP(nn.Module):
    """Top-k gated mixture of expert MLPs: the one expert layer.

    Args:
      num_experts: E, the router's width.
      mlp_ratio / expert_dim: an expert's hidden width
        (``expert_dim`` or ``d_model * mlp_ratio``).
      top_k: experts per token (1 = Switch, 2 = GShard default).
      capacity_factor: per-expert slots = ceil(top_k * N / E * factor);
        overflow tokens are dropped (their combine weight is zero), the
        standard Switch behavior, through ``ops.moe_dispatch_combine``.
        ``None`` drops nothing: the (token, choice) pairs routed to held
        experts take slots ordered by expert and, inside an expert, by
        pair number ``t k + j``, and the expert matmuls are
        ``ops.grouped_matmul`` over whatever group sizes the router
        made.  The buffers round them hold :func:`slot_bound`
        rows: twice the share of the pairs a balanced router sends to
        the held experts (a quarter of the pairs where an eighth of the
        experts is held; a slot a pair where all are, or at small
        shapes).  A call whose router sends more runs further windows
        of that many slots, one after another, so it is slower and
        still exact.  The bound follows from the shapes; nothing sets it.
        **A window's plan** is the groups' ``sizes`` and, for each of its
        ``cap`` slots, the pair's token and gate value.  Where the
        buffers are shorter than the pairs (``cap < n k``: the layer
        holds under half the experts) it is made by counting
        (:func:`_count_blocks`, :func:`_window_plan`): compares, sums
        and one-hot products over whole arrays, for the window's slots
        alone; no array as long as the pairs is sorted, gathered from a
        number at a time or scattered into, forward or backward.  Where
        the buffers hold a slot a pair (``cap == n k``) the plan is a
        stable sort of the pairs, the right algorithm there (counting
        would cost a table of experts x pairs), and the program is the
        one it always was.  The choice follows from the shapes too.
      held: ``(first, count)``: the experts this layer holds out of
        ``num_experts`` (expert parallelism's share; ``None`` = all).
        Every token is still routed over all E; the layer computes
        ``sum p_e E_e(x)`` over the held experts only, and what the
        others would add is left out.  Needs ``capacity_factor=None``.
      gated: experts (and the shared MLP) are SiLU-gated,
        ``(silu(x W_gate) * (x W_in)) W_out``; else GELU, no gate.
      shared_dim: > 0 adds a shared MLP of this width every token passes
        through (shared experts, built as one MLP).
      shared_token_gate: the shared MLP's output is multiplied, token by token,
        by ``sigmoid(x w_sg)``, ``w_sg`` (d, 1) the leaf
        ``shared_expert_gate/kernel`` (Qwen's gated shared expert).
      renormalize: chosen gates are rescaled to sum to 1 (GShard);
        False keeps the router's probabilities as they are.
      scoring: ``"softmax"`` over the router's outputs, or ``"sigmoid"``
        of each on its own: the chosen scores are then divided by their
        sum + 1e-6 (``renormalize``) and multiplied by ``routed_scale``.
      select_bias: sigmoid scoring chooses by score + a bias an expert
        (the float32 leaf ``expert_bias``) and weighs by the score alone:
        the bias moves choices, never weights, so its gradient is zero
        and a step leaves it as it is (its update rule is a balancing
        heuristic outside the objective, not built here).
      seq_aux: the balance loss is taken per sequence over all top-k
        choices (``f_be = count * E / (k T)``, ``P_be = mean_t p``,
        ``mean_b sum_e f P``); else Switch's top-1 form over the batch.
      aux_loss_weight: weight of the balance loss, stored in the
        ``aux_loss`` mutable collection for the train step to pick up.

    With ``capacity_factor=None`` the layer also sows, for the step's
    metrics window, the counters ``moe/assignments_here``,
    ``moe/rows_computed``, ``moe/slot_rows`` (rows its buffers carried),
    ``moe/overflow_calls`` (calls that ran more than one window) and, sown
    only where the plan was made by counting, ``moe/counted_routes`` (such
    calls), and the gauge ``moe/expert_load_max_over_mean``; with
    ``select_bias`` the
    counters ``moe/bias_choices`` (the pairs it chose) and
    ``moe/bias_moved_choices`` (those whose expert is not among the
    ``top_k`` by score alone) (OBSERVABILITY.md).
    """

    num_experts: int = 8
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float | None = 1.25
    aux_loss_weight: float = 1e-2
    dtype: Any = jnp.float32
    expert_dim: int = 0
    held: tuple | None = None
    gated: bool = False
    shared_dim: int = 0
    shared_token_gate: bool = False
    renormalize: bool = True
    seq_aux: bool = False
    scoring: str = "softmax"
    select_bias: bool = False
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        *lead, d = x.shape
        n = 1
        for s in lead:
            n *= s
        tokens = x.reshape(n, d)
        e = self.num_experts
        k = min(self.top_k, e)
        first, count = self.held if self.held is not None else (0, e)
        if self.held is not None and self.capacity_factor is not None:
            raise ValueError("held experts need the no-drop layer "
                             "(capacity_factor=None)")
        if not (0 <= first and first + count <= e and count > 0):
            raise ValueError(f"held={self.held} does not lie in {e} experts")

        # --- routing ----------------------------------------------------
        with jax.named_scope("tpuframe/moe/route"):
            logits = nn.Dense(
                e, use_bias=False, dtype=jnp.float32, name="router"
            )(tokens.astype(jnp.float32))
            if self.scoring not in ("softmax", "sigmoid"):
                raise ValueError(f"scoring={self.scoring!r}: softmax or sigmoid")
            if self.select_bias and self.scoring != "sigmoid":
                raise ValueError("select_bias is sigmoid scoring's")
            if self.scoring == "sigmoid":
                probs = None
                gate_vals, gate_idx = self._sigmoid_choices(logits, k)
            else:
                probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
                # top-k expert choices per token
                gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (N, k)
                if self.renormalize:
                    # chosen gates sum to 1 (GShard convention)
                    gate_vals = gate_vals / jnp.maximum(
                        jnp.sum(gate_vals, -1, keepdims=True), 1e-9
                    )

        h = self.expert_dim or d * self.mlp_ratio
        init = nn.initializers.lecun_normal()
        # float32 master weights like every other layer's (the policy casts
        # them for compute); the products run in the layer's dtype
        w_gate = (self.param("w_gate", init, (count, d, h)).astype(self.dtype)
                  if self.gated else None)
        w_in = self.param("w_in", init, (count, d, h)).astype(self.dtype)
        w_out = self.param("w_out", init, (count, h, d)).astype(self.dtype)
        act = nn.silu if self.gated else nn.gelu

        if self.capacity_factor is not None:
            # --- dispatch / expert MLPs / combine, capacity-truncated ----
            # tpuframe.ops.moe_gating owns the mechanics: the fused path
            # scatter-adds kept tokens straight into the (E, C, D) expert
            # buffers (no (kN, E, C) one-hot tensor); the dense-einsum
            # reference is the oracle.
            if self.gated:
                raise ValueError("gated experts run in the no-drop layer "
                                 "(capacity_factor=None)")
            capacity = max(1, int(-(-(k * n) // e) * self.capacity_factor))
            out = moe_dispatch_combine(
                tokens, gate_vals, gate_idx, w_in, w_out,
                capacity=capacity, act=act,
            )
        else:
            out = self._no_drop(tokens, gate_vals, gate_idx, first, count,
                                w_gate, w_in, w_out, act)

        if self.shared_dim:
            with jax.named_scope("tpuframe/moe/shared"):
                dense = lambda m, name: nn.Dense(  # noqa: E731
                    m, use_bias=not self.gated, dtype=self.dtype, name=name
                )
                t = tokens.astype(self.dtype)
                hid = dense(self.shared_dim, "shared_in")(t)
                hid = (act(dense(self.shared_dim, "shared_gate")(t)) * hid
                       if self.gated else act(hid))
                shared = dense(d, "shared_out")(hid)
                if self.shared_token_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype, name="shared_expert_gate")(t))
                out = out + shared.astype(out.dtype)

        # --- load-balance aux loss ---------------------------------------
        if probs is None:
            # sigmoid scores are no distribution over the experts: the
            # models that score so balance by the selection bias
            return out.reshape(*lead, d).astype(x.dtype)
        if self.seq_aux:
            # per sequence, over all k choices (DeepSeek-V2 eq. 12-14)
            b = lead[0] if len(lead) > 1 else 1
            chosen = jnp.sum(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=1)
            f = jnp.sum(chosen.reshape(b, -1, e), axis=1) * (e / (k * (n // b)))
            p_mean = jnp.mean(probs.reshape(b, -1, e), axis=1)
            aux = jnp.mean(jnp.sum(f * p_mean, axis=-1)) * self.aux_loss_weight
        else:
            # Switch eq. 4: fraction of tokens routed to each expert (by
            # top-1 choice) x mean router prob; scaled by E so balanced = 1.0
            top1 = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
            aux = jnp.sum(
                jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0)
            ) * e * self.aux_loss_weight
        self.sow("aux_loss", "moe", aux)

        return out.reshape(*lead, d).astype(x.dtype)

    def _sigmoid_choices(self, logits, k):
        """(weights, experts), each (N, k): every expert scored on its
        own; chosen by score (+ the selection bias), weighed by score."""
        if self.aux_loss_weight:
            raise ValueError("sigmoid scoring takes aux_loss_weight=0: no "
                             "balance loss is defined on its scores")
        scores = jax.nn.sigmoid(logits)
        if self.select_bias:
            bias = self.param("expert_bias", nn.initializers.zeros,
                              (logits.shape[-1],), jnp.float32)
            _, gate_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
            gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
            unmoved = gate_idx[:, :, None] == jax.lax.top_k(scores, k)[1][:, None, :]
            for name, value in (
                    ("moe/bias_choices", gate_idx.size),
                    ("moe/bias_moved_choices", jnp.sum(~jnp.any(unmoved, axis=-1)))):
                self.sow("counters", name, jnp.float32(value),
                         reduce_fn=lambda a, b: b, init_fn=lambda: jnp.float32(0))
        else:
            gate_vals, gate_idx = jax.lax.top_k(scores, k)
        if self.renormalize:
            gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-6)
        return gate_vals * self.routed_scale, gate_idx

    def _no_drop(self, tokens, gate_vals, gate_idx, first, count,
                 w_gate, w_in, w_out, act):
        """``sum p_e E_e(x)`` over the held experts, no token dropped:
        the slot buffers' bound from the shapes, then the route by the
        branch the bound names (sorted where a slot a pair, counted where
        fewer), the experts, and the layer's counters."""
        n, k = gate_idx.shape
        pairs = n * k
        # the buffers hold the pairs routed here, not a slot a pair: twice
        # a balanced router's share, and a call that gets more runs window
        # after window of them (_experts_windowed)
        cap = slot_bound(pairs, count, self.num_experts)
        with jax.named_scope("tpuframe/moe/route"):
            # a pair's key: its held expert, or `count` where routed elsewhere
            local = gate_idx.reshape(-1) - first
            here = (local >= 0) & (local < count)
            key = jnp.where(here, local, count)
        if cap == pairs:
            # every expert held (or small shapes): a slot a pair, and the
            # route is a stable sort of the pairs by expert
            with jax.named_scope("tpuframe/moe/route"):
                # one slot for every (token, choice) pair; the pairs routed
                # to held experts sort to the front, grouped by expert
                order = jnp.argsort(key, stable=True)
                inv = jnp.argsort(order)
                sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
                tok = order // k
                weight = (gate_vals.reshape(-1) * here)[order]
            with jax.named_scope("tpuframe/moe/experts"):
                out = _expert_parts(tokens.astype(self.dtype), w_gate, w_in, w_out, weight,
                                    tok, inv, sizes, 0, pairs, act)[0]
        else:
            # fewer slots than pairs: a window's plan is made by counting,
            # for its `cap` slots alone (_window_plan); nothing `pairs` long
            # is sorted, gathered from or scattered into
            with jax.named_scope("tpuframe/moe/route"):
                sizes, edges = _count_blocks(key, count)
            with jax.named_scope("tpuframe/moe/experts"):
                out = _experts_windowed(tokens.astype(self.dtype), w_gate, w_in, w_out,
                                        gate_vals.reshape(-1), key, sizes, edges, cap, k, act)
        with jax.named_scope("tpuframe/moe/experts"):
            windows = _windows(sizes, cap)
        f32 = jnp.float32
        load = sizes.astype(f32)

        def sow(collection, name, value):
            self.sow(collection, name, value.astype(f32),
                     reduce_fn=lambda a, b: b, init_fn=lambda: f32(0))

        sow("counters", "moe/assignments_here", jnp.sum(load))
        # in row tiles of the product that ran: the kernels' own, or ragged_dot's
        tile = row_tile(cap, tokens.shape[1], w_in.shape[2], self.dtype)
        sow("counters", "moe/rows_computed", tiles_visited(sizes, tile) * tile)
        sow("counters", "moe/slot_rows", windows * cap)
        sow("counters", "moe/overflow_calls", windows > 1)
        if cap < pairs:  # sown where it counts: the sorted route's program stays as it was
            sow("counters", "moe/counted_routes", jnp.ones(()))
        sow("gauges", "moe/expert_load_max_over_mean",
            jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0))
        return out
