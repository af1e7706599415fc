"""Blockwise (flash-style) single-device attention: linear-in-L memory.

Ring attention (`tpuframe.ops.ring_attention`) spreads the sequence over
chips; this is the within-one-shard counterpart for long context that
FITS on a chip but whose (B, H, L, L) score matrix would not — forward
AND backward:

- **Forward**: an outer ``lax.scan`` over Q blocks runs the inner
  online-softmax K/V scan (`ring_attention._block_update` — one
  numerics implementation, ring and blockwise schedules share it) and
  emits, besides the normalized output, each row's logsumexp.
- **Backward**: hand-written (``jax.custom_vjp``), the FlashAttention-2
  two-pass recipe.  Reverse-mode through the scan-of-scans stacked
  per-step residuals and re-ran the whole inner sweep per Q block —
  measured 107.6 ms fwd+bwd per layer at seq 8192 on v5e vs 13.0 ms
  forward (PERF.md r03).  Instead the VJP saves only Q/K/V, the output
  and the O(L) logsumexp, and recomputes probabilities one
  (block x block) tile at a time: pass 1 scans Q blocks accumulating
  dQ; pass 2 scans K/V blocks accumulating dK/dV.
- Q/K/V keep their storage dtype end to end: the MXU multiplies bf16
  natively with f32 accumulation; only softmax state (and the gradient
  accumulators) are f32.
- L pads up to a block multiple (padded keys are masked via ``kv_len``,
  padded query rows are sliced off) — one MXU-friendly compiled
  schedule for any L, never a degenerate tiny-block divisor.

Causal note: tiles entirely above the diagonal are *skipped at
runtime* — the scan bodies branch on the scalar block indices with
``lax.cond`` (a real XLA Conditional, not a select), so the causal
sweep executes only the ~(n^2+n)/2 tiles that intersect the triangle
while keeping one static schedule.  Diagonal tiles still mask
element-wise.

``TransformerLM(attn_impl="blockwise")`` selects it; composes with the
``seq``-sharded impls (they shard ACROSS devices, this blocks WITHIN
one).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from tpuframe.ops.ledger import attn_block
from tpuframe.ops.ring_attention import _block_update, _causal_skip, _tile_grads

__all__ = ["blockwise_attention"]


def _to_blocks(a, n, block):
    b, _, h, d = a.shape
    return a.reshape(b, n, block, h, d).transpose(1, 0, 2, 3, 4)


def _from_blocks(a):
    n, b, block, h, d = a.shape
    return a.transpose(1, 0, 2, 3, 4).reshape(b, n * block, h, d)


def _fwd_schedule(q_blocks, k_blocks, v_blocks, causal, scale, block, kv_len):
    """Online-softmax forward over blocks -> (out_blocks, lse_blocks)."""
    n, b, _, h, _ = q_blocks.shape
    dv = v_blocks.shape[-1]  # the output takes the values' width
    block_pos = jnp.arange(block)

    def q_body(q_blk, q_idx):
        q_pos = q_idx * block + block_pos
        init = (
            jnp.zeros((b, block, h, dv), jnp.float32),
            jnp.zeros((b, h, block), jnp.float32),
            jnp.full((b, h, block), -jnp.inf, jnp.float32),
        )

        def kv_body(carry, xs):
            k_blk, v_blk, k_idx = xs

            def update(c):
                return _block_update(
                    q_blk, k_blk, v_blk, *c,
                    q_pos, k_idx * block + block_pos,
                    causal, scale, kv_len=kv_len,
                )

            # tiles entirely above the diagonal are SKIPPED at runtime,
            # not just masked — ~half the causal sweep never executes
            carry = _causal_skip(
                (k_idx <= q_idx) if causal else None, update, carry
            )
            return carry, None

        (o, lsum, m), _ = lax.scan(
            kv_body, init, (k_blocks, v_blocks, jnp.arange(n))
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
        lambda _, xs: (None, q_body(*xs)), None, (q_blocks, jnp.arange(n))
    )
    return outs, lses  # (n, B, blk, H, D) storage dtype, (n, B, H, blk) f32


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blockwise_padded(q, k, v, causal, block, kv_len, scale):
    out, _ = _blockwise_padded_fwd(q, k, v, causal, block, kv_len, scale)
    return out


def _blockwise_padded_fwd(q, k, v, causal, block, kv_len, scale):
    b, l_pad, h, d = q.shape
    n = l_pad // block
    outs, lses = _fwd_schedule(
        _to_blocks(q, n, block), _to_blocks(k, n, block),
        _to_blocks(v, n, block), causal, scale, block, kv_len,
    )
    out = _from_blocks(outs).astype(q.dtype)
    return out, (q, k, v, out, lses)


def _blockwise_padded_bwd(causal, block, kv_len, scale, res, g):
    q, k, v, out, lses = res
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

    # Pass 1: dQ.  Outer scan over Q blocks (ys only), inner scan over
    # K/V blocks with a (B, blk, H, D) f32 accumulator.
    def dq_body(q_blk, do_blk, lse_blk, delta_blk, q_idx):
        q_pos = q_idx * block + block_pos

        def inner(dq, xs):
            k_blk, v_blk, k_idx = xs

            def update(dq):
                _, ds = _tile_grads(
                    q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                    q_pos, k_idx * block + block_pos, causal, scale, kv_len,
                )
                return dq + jnp.einsum(
                    "bhqk,bkhd->bqhd", ds.astype(k_blk.dtype), k_blk,
                    preferred_element_type=jnp.float32,
                )

            dq = _causal_skip((k_idx <= q_idx) if causal else None, update, dq)
            return dq, None

        dq0 = jnp.zeros((b, block, h, d), jnp.float32)
        dq, _ = lax.scan(inner, dq0, (k_blocks, v_blocks, idx))
        return dq

    _, dq_blocks = lax.scan(
        lambda _, xs: (None, dq_body(*xs)), None,
        (q_blocks, do_blocks, lses, delta_blocks, idx),
    )

    # Pass 2: dK/dV.  Outer scan over K/V blocks, inner over Q blocks.
    def dkv_body(k_blk, v_blk, k_idx):
        k_pos = k_idx * block + block_pos

        def inner(carry, xs):
            q_blk, do_blk, lse_blk, delta_blk, q_idx = xs

            def update(c):
                dk, dv = c
                p, ds = _tile_grads(
                    q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                    q_idx * block + block_pos, k_pos, causal, scale, kv_len,
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

            carry = _causal_skip(
                (q_idx >= k_idx) if causal else None, update, carry
            )
            return carry, None

        zero_k = jnp.zeros((b, block, h, d), jnp.float32)
        zero_v = jnp.zeros((b, block, h, v.shape[-1]), jnp.float32)
        (dk, dv), _ = lax.scan(
            inner, (zero_k, zero_v),
            (q_blocks, do_blocks, lses, delta_blocks, idx),
        )
        return dk, dv

    _, (dk_blocks, dv_blocks) = lax.scan(
        lambda _, xs: (None, dkv_body(*xs)), None, (k_blocks, v_blocks, idx)
    )

    dq = _from_blocks(dq_blocks).astype(q.dtype)
    dk = _from_blocks(dk_blocks).astype(k.dtype)
    dv = _from_blocks(dv_blocks).astype(v.dtype)
    return dq, dk, dv


_blockwise_padded.defvjp(_blockwise_padded_fwd, _blockwise_padded_bwd)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_size: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention over (B, L, H, D) without materializing (.., L, L).

    ``scale`` replaces the default ``1/sqrt(D)``; ``v`` may have a width
    of its own (latent attention: 192-wide queries and keys, 128-wide
    values), which the output takes.

    ``block_size`` defaults to the domain-clamped
    ``TPUFRAME_KERNEL_ATTN_BLOCK`` knob (512) — the tile the kernel
    ledger probes over its legal grid; an explicit value always wins.
    """
    if block_size is None:
        block_size = attn_block()
    b, l, h, d = q.shape
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            f"q/k/v shapes must match, got {q.shape}/{k.shape}/{v.shape}"
        )
    block = min(block_size, l)
    n = -(-l // block)
    l_pad = n * block
    if l_pad != l:
        pad = [(0, 0), (0, l_pad - l), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    out = _blockwise_padded(q, k, v, causal, block, l, float(scale))
    return out[:, :l]
