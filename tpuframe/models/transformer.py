"""Decoder-only transformer LM with sequence-parallel ring attention.

The reference repo is vision-only (SURVEY.md §5: no attention models
anywhere), but long-context training is first-class in tpuframe: this
family is the workload that exercises the ``seq`` mesh axis.  Design:

- NHWC-free (B, L, D) layout; bf16-ready via ``dtype``.
- Attention dispatch: ``attn_impl="auto"`` uses exact ring attention
  (`tpuframe.ops.ring_attention`) whenever the current mesh shards the
  sequence axis — K/V rotate the ICI ring, scores never materialize
  globally; unsharded sequences of ``_BLOCKWISE_AUTO_LEN`` (4k) tokens
  or more take the flash-style linear-memory blockwise path; short
  unsharded sequences use plain XLA attention.
- Tensor-parallel ready: :func:`transformer_tp_rules` gives the
  ParallelPlan rules that split QKV/MLP projections over ``model``
  (Megatron-style column->row pairing; XLA inserts the all-reduces).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import (
    DATA_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    current_runtime,
)
from tpuframe.ops.ring_attention import attention_reference, ring_attention_local
from tpuframe.ops.layer_norm import FusedLayerNorm
from tpuframe.ops.ulysses import ulysses_attention_local

#: attn_impl="auto" switches full -> blockwise at this unsharded sequence
#: length: 4k tokens is a 64 MB f32 score matrix PER (batch, head) — the
#: materialization, not the FLOPs, starts to dominate HBM there.
_BLOCKWISE_AUTO_LEN = 4096


def transformer_tp_rules():
    """ParallelPlan TP rules: column-parallel QKV/fc1, row-parallel out/fc2
    (≈ Megatron sharding, expressed declaratively)."""
    return (
        (r"(query|key|value)/kernel", P(None, MODEL_AXIS)),
        (r"attn_out/kernel", P(MODEL_AXIS, None)),
        (r"mlp_in/kernel", P(None, MODEL_AXIS)),
        (r"mlp_out/kernel", P(MODEL_AXIS, None)),
        (r"embed/embedding", P(None, MODEL_AXIS)),
        (r"lm_head/kernel", P(None, MODEL_AXIS)),
    )


def _mesh_or_none():
    try:
        return current_runtime(auto_init=False).mesh
    except RuntimeError:
        return None


class SelfAttention(nn.Module):
    """Causal multi-head self-attention with ring/full dispatch."""

    num_heads: int
    head_dim: int
    causal: bool = True
    #: "auto" picks ring attention when the mesh shards the sequence axis
    #: (no head-count constraint), blockwise for unsharded sequences of
    #: _BLOCKWISE_AUTO_LEN+ tokens, full otherwise; "ulysses" opts into
    #: the all-to-all form
    #: (tpuframe.ops.ulysses — one re-shard instead of N-1 ppermute hops,
    #: needs num_heads divisible by the seq-axis size); "blockwise" is the
    #: single-shard flash-style O(L*block) path
    #: (tpuframe.ops.blockwise_attention) for long context on one chip.
    attn_impl: str = "auto"  # "auto" | "full" | "ring" | "ulysses" | "blockwise"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        features = self.num_heads * self.head_dim
        dense = lambda name: nn.Dense(  # noqa: E731
            features, use_bias=False, dtype=self.dtype, name=name
        )
        b, l, _ = x.shape
        heads = (b, l, self.num_heads, self.head_dim)
        q = dense("query")(x).reshape(heads)
        k = dense("key")(x).reshape(heads)
        v = dense("value")(x).reshape(heads)

        impl = self.attn_impl
        mesh = _mesh_or_none()
        if self.is_initializing():
            # init traces with a sample batch that need not divide the mesh;
            # attention has no params, so the full path initializes
            # identically to ring.
            impl = "full"
        elif impl == "auto":
            seq_sharded = mesh is not None and mesh.shape.get(SEQUENCE_AXIS, 1) > 1
            if seq_sharded:
                impl = "ring"
            else:
                # measured first: the kernel ledger's priced verdict for
                # this seq-length shape class (bench_attention persists
                # them); the static memory-hazard heuristic is only the
                # fallback when nothing has been measured here
                from tpuframe.ops.ledger import attention_choice

                impl = attention_choice(l)
                if impl is None:
                    # long unsharded context: the (B,H,L,L) score matrix
                    # is the memory hazard; take the flash-style
                    # linear-memory path
                    impl = (
                        "blockwise" if l >= _BLOCKWISE_AUTO_LEN else "full"
                    )
        if impl in ("ring", "ulysses"):
            if mesh is None:
                raise ValueError(
                    f"attn_impl={impl!r} needs an initialized runtime mesh"
                )
            if impl == "ulysses":
                # the all-to-all owns the head dim during attention, so no
                # head_axis sharding here (TP composes via the projections)
                local_fn = ulysses_attention_local
                head_axis = None
            else:
                local_fn = ring_attention_local
                head_axis = MODEL_AXIS if (
                    mesh.shape.get(MODEL_AXIS, 1) > 1
                    and self.num_heads % mesh.shape[MODEL_AXIS] == 0
                ) else None
            spec = P((DATA_AXIS, FSDP_AXIS), SEQUENCE_AXIS, head_axis, None)
            out = shard_map(
                lambda q, k, v: local_fn(q, k, v, causal=self.causal),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        elif impl == "blockwise":
            from tpuframe.ops.blockwise_attention import blockwise_attention

            out = blockwise_attention(q, k, v, causal=self.causal)
        elif impl == "full":
            out = attention_reference(q, k, v, causal=self.causal)
        else:
            raise ValueError(
                f"unknown attn_impl {impl!r}; known: auto, full, ring, "
                "ulysses, blockwise"
            )
        out = out.reshape(b, l, features)
        return nn.Dense(
            x.shape[-1], use_bias=False, dtype=self.dtype, name="attn_out"
        )(out)


class Block(nn.Module):
    """Pre-norm transformer block: LN -> attn -> +res, LN -> MLP -> +res.

    ``moe_experts > 0`` replaces the dense MLP with a top-k gated
    MoE (GShard pattern): expert weights shard over the ``expert`` mesh
    axis via ``moe_rules`` and the router's load-balancing loss rides the
    ``aux_loss`` collection into the train objective.
    """

    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    causal: bool = True
    attn_impl: str = "auto"
    dtype: Any = jnp.float32
    #: False when the block runs inside an existing shard_map (GPipe):
    #: the fused LN must not open a nested shard_map there.
    ln_use_mesh: bool = True
    moe_experts: int = 0
    moe_top_k: int = 2

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        d = x.shape[-1]
        ln = lambda name: FusedLayerNorm(  # noqa: E731
            dtype=self.dtype, use_mesh=self.ln_use_mesh, name=name
        )
        y = ln("ln1")(x)
        y = SelfAttention(
            self.num_heads, self.head_dim, causal=self.causal,
            attn_impl=self.attn_impl, dtype=self.dtype, name="attn",
        )(y, train=train)
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = ln("ln2")(x)
        if self.moe_experts:
            from tpuframe.models.moe import MoEMLP

            y = MoEMLP(
                num_experts=self.moe_experts, top_k=self.moe_top_k,
                mlp_ratio=self.mlp_ratio, dtype=self.dtype, name="moe",
            )(y, train=train)
        else:
            y = nn.Dense(
                d * self.mlp_ratio, dtype=self.dtype, name="mlp_in"
            )(y)
            y = nn.gelu(y)
            y = nn.Dense(d, dtype=self.dtype, name="mlp_out")(y)
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return x + y


#: Block with backward-pass rematerialization (jax.checkpoint); the static
#: index pins ``train`` (arg 2: module, x, train) — single definition so
#: callers can't drift from Block.__call__'s positional signature.
RematBlock = nn.remat(Block, static_argnums=(2,))


class TransformerLM(nn.Module):
    """Decoder-only LM: (B, L) int tokens -> (B, L, vocab) logits.

    ``remat=True`` rematerializes each block in the backward pass
    (``jax.checkpoint`` via ``nn.remat``): activation memory drops from
    O(layers) to O(1) blocks at ~1/3 extra FLOPs — the standard trade
    for long-context or memory-bound configs.  Numerics are identical.
    """

    vocab_size: int
    num_layers: int = 4
    num_heads: int = 8
    head_dim: int = 32
    max_len: int = 2048
    mlp_ratio: int = 4
    dropout: float = 0.0
    attn_impl: str = "auto"
    dtype: Any = jnp.float32
    remat: bool = False
    #: >0 swaps every block's dense MLP for a top-k gated MoE (GShard);
    #: compose with ParallelPlan(rules=moe_rules()) for expert parallelism
    moe_experts: int = 0
    moe_top_k: int = 2

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False) -> jax.Array:
        d_model = self.num_heads * self.head_dim
        x = nn.Embed(self.vocab_size, d_model, dtype=self.dtype, name="embed")(tokens)
        pos = nn.Embed(self.max_len, d_model, dtype=self.dtype, name="pos_embed")(
            jnp.arange(tokens.shape[1])[None, :]
        )
        x = x + pos
        block_cls = RematBlock if self.remat else Block
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads, self.head_dim, mlp_ratio=self.mlp_ratio,
                dropout=self.dropout, causal=True, attn_impl=self.attn_impl,
                dtype=self.dtype, moe_experts=self.moe_experts,
                moe_top_k=self.moe_top_k, name=f"block{i}",
            )(x, train)
        x = FusedLayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head"
        )(x)
        return logits.astype(jnp.float32)
