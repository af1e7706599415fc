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

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Full (unsharded) attention oracle, (B, L, H, D) layout.

    ``scale`` replaces the default ``1/sqrt(D)`` (latent attention folds
    its rotary scaling into it); ``v`` may be narrower or wider than
    ``q``/``k``: the output takes ``v``'s width."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(ki <= qi, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_update(q, k, v, o, l, m, q_pos, k_pos, causal, scale,
                  kv_len: int | None = None):
    """Online-softmax accumulation of one K/V block into (o, l, m).

    ``kv_len`` masks padded key positions (``k_pos >= kv_len``) — used by
    the blockwise schedule, which pads the sequence to a block multiple.

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
        mask = k_pos[None, :] <= q_pos[:, None]  # (Lq, Lk)
        s = jnp.where(mask[None, None], s, -jnp.inf)
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
                q_pos, k_pos, causal, scale, kv_len=None):
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
        cmask = k_pos[None, :] <= q_pos[:, None]
        valid = cmask if valid is None else (valid & cmask)
    if valid is not None:
        s = jnp.where(valid[None, None], s, -jnp.inf)
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
