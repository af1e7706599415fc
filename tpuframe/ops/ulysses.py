"""Ulysses-style all-to-all sequence parallelism (SP alternative to ring).

The second of the two canonical long-context strategies (absent from the
vision-only reference — SURVEY.md §5 — but first-class here).  Where ring
attention keeps Q local and rotates K/V around the ``seq`` axis with
``axis_size`` ppermute hops, the all-to-all form (DeepSpeed-Ulysses
pattern) re-shards *once*: an all-to-all swaps the sequence sharding for a
head sharding, every device runs plain full attention over the whole
sequence for its subset of heads, and a second all-to-all swaps back.

Trade-offs (why both exist):

- Ulysses: 2 all-to-alls per tensor (4 collectives total incl. the output)
  regardless of axis size, and the attention itself is a single dense
  block XLA can tile perfectly — but it needs ``num_heads %% axis_size == 0``
  and materializes full-sequence scores per head-shard, O(L^2 / N) memory.
- Ring: no head-count constraint and O((L/N)^2) score memory — the choice
  for extreme sequence lengths — but pays ``axis_size - 1`` ppermute hops.

Layout contract matches ring attention: per-device shards
(batch, seq_local, heads, head_dim); global sequence is the concatenation
of shards in ``seq``-axis index order (which is exactly the peer order
``lax.all_to_all`` concatenates in, so causal masking needs no index
bookkeeping — after the first all-to-all every device sees the full
sequence in global order).
"""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS
from tpuframe.ops.ring_attention import attention_reference


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = False,
) -> jax.Array:
    """Per-device Ulysses body (call under shard_map).

    Args are this device's sequence shards, (B, L_local, H, D); returns
    the same shard layout.  Exact — identical to full attention.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return attention_reference(q, k, v, causal=causal)
    heads = q.shape[2]
    if heads % n:
        raise ValueError(
            f"ulysses attention needs num_heads ({heads}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring attention otherwise"
        )
    # seq-sharded -> head-sharded: (B, L/N, H, D) -> (B, L, H/N, D)
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    out = attention_reference(a2a(q), a2a(k), a2a(v), causal=causal)
    # head-sharded -> seq-sharded: (B, L, H/N, D) -> (B, L/N, H, D)
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    causal: bool = False,
    seq_axis: str = SEQUENCE_AXIS,
    batch_axes=(DATA_AXIS, FSDP_AXIS),
) -> jax.Array:
    """shard_map wrapper: global (B, L, H, D) arrays over ``mesh``.

    Batch splits over ``batch_axes``, sequence over ``seq_axis``.  (No
    ``head_axis`` option: the all-to-all itself owns the head dimension
    during attention — combine with tensor parallelism by giving the
    attention projections TP rules instead.)
    """
    spec = P(tuple(batch_axes), seq_axis, None, None)
    fn = functools.partial(ulysses_attention_local, axis_name=seq_axis, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
