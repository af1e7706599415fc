"""Decoder-only transformer LM with sequence-parallel ring attention.

The reference repo is vision-only (SURVEY.md §5: no attention models
anywhere), but long-context training is first-class in tpuframe: this
family is the workload that exercises the ``seq`` mesh axis.  Design:

- NHWC-free (B, L, D) layout; bf16-ready via ``dtype``.
- Attention dispatch: ``attn_impl="auto"`` is one rule
  (:func:`_resolve_impl`).  The mesh shards the sequence axis: exact
  ring attention (`tpuframe.ops.ring_attention`) — K/V rotate the ICI
  ring, scores never materialize globally.  Else, from
  ``_FLASH_AUTO_LEN`` positions on, wherever the Pallas flash kernels
  of `tpuframe.ops.blockwise_attention` would run for the call (a TPU:
  one device, a manual region, or per shard on a mesh whose batch axes
  divide the batch and whose model axis divides the heads): the
  kernels, under ``shard_map`` on a mesh.  Else from
  ``_BLOCKWISE_AUTO_LEN`` (4k) positions on the same op's scan
  schedule, for its linear memory.  Else plain XLA attention
  (``attention_reference``): the fallback, and the oracle.
- Tensor-parallel ready: :func:`transformer_tp_rules` gives the
  ParallelPlan rules that split QKV/MLP projections over ``model``
  (Megatron-style column->row pairing; XLA inserts the all-reduces).
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpuframe.core.runtime import (
    DATA_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    current_runtime,
)
from tpuframe.ops.dispatch import batch_sharding_info, effective_mesh
from tpuframe.ops.gated_delta import chunks_walked, gated_delta, gated_delta_reference
from tpuframe.ops.head_norm_rope import head_norm_rope, head_norm_rope_reference
from tpuframe.ops.kda import kda, kda_reference
from tpuframe.ops.ring_attention import (
    SelectedKeysMask,
    SlidingWindowMask,
    attention_reference,
    mask_or_causal,
    ring_attention_local,
)
from tpuframe.ops.layer_norm import FusedLayerNorm
from tpuframe.ops.sparse_index import select_keys, select_keys_reference
from tpuframe.ops.short_conv import (
    conv_silu,
    conv_silu_reference,
    short_conv,
    short_conv_reference,
)
from tpuframe.ops.ulysses import ulysses_attention_local
from tpuframe.track.telemetry import get_telemetry

# the module, by path: ``tpuframe.ops`` rebinds the name to the function
_blockwise = importlib.import_module("tpuframe.ops.blockwise_attention")

#: attn_impl="auto" takes blockwise attention's *scan schedule* (where
#: its flash kernels do not run: a CPU, TPUFRAME_DISABLE_PALLAS, a batch
#: that does not divide the mesh) from this unsharded sequence length
#: on, for memory: 4k tokens is a 64 MB f32 score matrix PER (batch,
#: head).  Below it the schedule loses to full attention (2.231 ms
#: against 2.177 a layer at GPT-2-medium's shape on the v5e, PR 28).
_BLOCKWISE_AUTO_LEN = 4096
#: ... and its *flash kernels*, wherever they would run, from this
#: length on: the shortest at which the kernels with the copies round
#: them beat full attention, forward + backward, on the v5e at batch 4
#: of 16 heads of 64 in bf16 (ms a layer, 24 chained, PR 30): 0.093 /
#: 0.027 at 128 positions, 0.188 / 0.059 at 256, 0.371 / 0.242 at 512,
#: 0.869 / 2.131 at 1024, 2.820 / 7.967 at 2048.  A shape rule like
#: the kernels' VMEM cliff, not a knob.
_FLASH_AUTO_LEN = 1024


def transformer_tp_rules():
    """ParallelPlan TP rules: column-parallel QKV/fc1, row-parallel out/fc2
    (≈ Megatron sharding, expressed declaratively)."""
    return (
        (r"(query|key|value)/kernel", P(None, MODEL_AXIS)),
        (r"attn_out/kernel", P(MODEL_AXIS, None)),
        (r"mlp_in/kernel", P(None, MODEL_AXIS)),
        (r"mlp_out/kernel", P(MODEL_AXIS, None)),
        # the short-convolution operator is elementwise between its
        # projections: [B | C | h] split by columns would part a column's
        # three, so the input projection stays whole and the output
        # projection splits its output columns
        (r"conv/out_proj/kernel", P(None, MODEL_AXIS)),
        # the gated delta rule has no split over the model axis yet (a head's
        # q, k, v, z, b and a lie in two fused projections, and the op takes
        # whole heads): the linear-attention layer's input projections stay
        # whole too, and its output projection splits its output columns
        (r"deltanet/out_proj/kernel", P(None, MODEL_AXIS)),
        # Kimi Delta Attention the same: the vector-decay rule takes whole
        # heads and has no split over the model axis yet, so its input
        # projection, the low-rank pairs and beta's stay whole (no rule names
        # them) and the output projection splits its output columns
        (r"kda/out_proj/kernel", P(None, MODEL_AXIS)),
        # the index of sparse attention chooses one set of keys for all heads
        # of a row: every device of the model axis needs all of it, so its
        # three projections (and the key norm, which no rule names) stay whole
        (r"attn/index_[qkw]/kernel", P()),
        (r"embed/embedding", P(None, MODEL_AXIS)),
        (r"lm_head/kernel", P(None, MODEL_AXIS)),
    )


def _mesh_or_none():
    try:
        return current_runtime(auto_init=False).mesh
    except RuntimeError:
        return None


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale``, statistics in float32;
    ``unit_offset``: ``* (1 + scale)``, the scale seeded 0 (Qwen3-Next's
    and Gemma's form of the same norm).

    Plain jnp on purpose: on a block's (B, L, D) rows XLA fuses it into
    its neighbours, and a kernel of its own would cost layout copies
    around it (PERF.md, PR 25).  A norm on every head before rotary
    positions is `HeadNormRope`'s."""

    eps: float = 1e-6
    dtype: Any = jnp.float32
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = _norm_scale(self, x.shape[-1], self.unit_offset)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


def _norm_scale(module, width: int, unit_offset: bool):
    """A norm's learned ``scale`` leaf as the factor it stands for."""
    if not unit_offset:
        return module.param("scale", nn.initializers.ones, (width,))
    return 1.0 + module.param("scale", nn.initializers.zeros, (width,))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_len: int,
                          beta_fast: float, beta_slow: float) -> tuple[int, int]:
    """(low, high): the rotary pairs between which YaRN blends scaled and
    unscaled frequencies; ``dim_of(n)`` is the pair that turns ``n`` times
    over the original context."""
    def dim_of(n):
        return dim * math.log(original_len / (2 * math.pi * n)) / (2 * math.log(theta))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def rope_inv_freq(dim: int, theta: float, scaling: dict | None = None) -> np.ndarray:
    """(dim/2,) inverse frequencies; ``scaling`` (a config's
    ``rope_scaling`` of type yarn) blends ``f/factor`` into ``f`` over the
    correction range."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return f
    low, high = yarn_correction_range(
        dim, theta, scaling["original_max_position_embeddings"],
        scaling["beta_fast"], scaling["beta_slow"])
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / scaling["factor"] * ramp + f * (1 - ramp)


def rope_tables(length: int, dim: int, theta: float,
                scaling: dict | None = None,
                positions: np.ndarray | None = None) -> tuple[jax.Array, jax.Array]:
    """(cos, sin), each (length, dim) float32, rotate-half convention.
    YaRN's factor on the tables, mscale / mscale_all_dim, is applied.
    ``positions`` (static, ``length`` of them) are the rows' position
    ids where they are not ``0 .. length - 1``."""
    if positions is None:
        positions = np.arange(length)
    ang = np.asarray(positions, np.float64)[:, None] * rope_inv_freq(dim, theta, scaling)
    ang = np.concatenate([ang, ang], axis=-1)
    m = 1.0
    if scaling:
        m = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0))
             / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0.0)))
        # a config that states the factor itself is taken at its word
        m = scaling.get("attention_factor") or m
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate (B, L, H, dim) by position: ``x cos + rotate_half(x) sin``;
    tables narrower than ``dim`` turn the first dimensions only."""
    width = cos.shape[-1]
    if width < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :width], cos, sin), x[..., width:]], axis=-1)
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :] + rot * sin[None, :, None, :]).astype(x.dtype)


class HeadNormRope(nn.Module):
    """An RMSNorm on every head of a projection's (B, L, H * D) rows,
    then the rotary turn, in one pass each way
    (`tpuframe.ops.head_norm_rope`: XLA makes of the two, on a
    (B, L, H, D) view, a handful of float32 passes and re-tiled
    residuals).  The scale is where `RMSNorm` keeps it in the tree, in
    either of its forms; tables narrower than a head turn its first
    dimensions and leave the others as the norm made them."""

    num_heads: int
    eps: float = 1e-6
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, rope) -> jax.Array:
        scale = _norm_scale(self, x.shape[-1] // self.num_heads, self.unit_offset)
        if self.is_initializing():
            # init's sample batch need not divide the mesh
            return head_norm_rope_reference(
                x, scale, *rope, num_heads=self.num_heads, eps=self.eps)
        return head_norm_rope(x, scale, *rope, num_heads=self.num_heads,
                              eps=self.eps, mesh=_mesh_or_none())


def _per_shard_spec(mesh, batch: int, num_heads: int):
    """The ``shard_map`` spec that hands each device of ``mesh`` whole
    (row, head) pairs of a (B, L, H, D) array whose sequence is not
    sharded: rows over the batch axes, heads over the model axis.  None
    where there is nothing to split (no mesh, one device, a manual
    region already: the bare call is the per-shard one) or where the
    batch or the heads do not divide."""
    mesh = effective_mesh(mesh)
    if mesh is None or mesh.size == 1:
        return None
    _, n_batch, _ = batch_sharding_info(mesh, None, batch)
    n_model = mesh.shape.get(MODEL_AXIS, 1)
    if batch % n_batch or num_heads % n_model:
        return None
    return P((DATA_AXIS, FSDP_AXIS), None,
             MODEL_AXIS if n_model > 1 else None, None)


@functools.partial(jax.jit, static_argnames=("mesh", "spec", "causal", "scale", "mask"))
def _blockwise_per_shard(q, k, v, *operands, mesh, spec, causal, scale, mask=None):
    """``blockwise_attention`` on each device's shard of q, k, v (and of the
    ``operands`` a mask rule reads: a device's rows, whole).  A jit
    of its own, so the layers of a model, which call it alike, trace and
    lower one region and not one each: 48 separate regions took the
    four-chip GPT-2-medium step 26 s to lower (PR 30)."""
    return shard_map(
        lambda q, k, v, *operands: _blockwise.blockwise_attention(
            q, k, v, causal=causal, scale=scale, mask=mask,
            **({"mask_operands": operands} if operands else {})),
        mesh=mesh, in_specs=(spec, spec, spec) + (P(spec[0], None, None),) * len(operands),
        out_specs=spec, check_vma=False,
    )(q, k, v, *operands)


def _resolve_impl(impl: str, q, mesh, initializing: bool,
                  per_shard: bool) -> str:
    """The attention form ``impl`` stands for, from what the call can
    see: the one rule behind ``attn_impl="auto"``.  ``per_shard``: the
    call can be placed per shard on the mesh (`_per_shard_spec`)."""
    if initializing:
        # init traces with a sample batch that need not divide the mesh;
        # attention has no params, so the full path initializes
        # identically to ring.
        return "full"
    if impl != "auto":
        return impl
    if mesh is not None and mesh.shape.get(SEQUENCE_AXIS, 1) > 1:
        return "ring"
    length = q.shape[1]
    # the flash kernels wherever they would run for this call and win:
    # the scores stay in VMEM
    if length >= _FLASH_AUTO_LEN and _blockwise.engage_kernels(
            q, shardable=per_shard) is not None:
        return "blockwise"
    # long unsharded context: the (B,H,L,L) score matrix is the memory
    # hazard; take the linear-memory scan schedule
    return "blockwise" if length >= _BLOCKWISE_AUTO_LEN else "full"


def _attend(q, k, v, *, impl: str, causal: bool, num_heads: int,
            initializing: bool, scale: float | None = None,
            mask=None, on_tiles=None, mask_operands=()) -> jax.Array:
    """The attention core every attention module dispatches to:
    (B, L, H, D) q/k and (B, L, H, Dv) v -> (B, L, H, Dv) by ``impl``.
    ``scale`` (None: ``1/sqrt(D)``), a value width of its own, a
    ``mask`` that is a rule on positions in ``causal``'s place
    (`ops.ring_attention`'s protocol; one that is the causal mask on
    this row runs as ``causal``; ``mask_operands`` are the arrays it reads
    where it reads any) and ``k``/``v`` of one head
    a group of query heads are taken by ``full`` and ``blockwise``; the
    sequence-sharded forms keep one head width, the default scale,
    ``causal`` and as many key/value heads as query heads.  ``on_tiles``
    is called with `blockwise_attention.tile_counts` of the call where
    the blockwise form runs it under a mask rule, kernels or schedule as
    decided here."""
    if mask is not None and mask_or_causal(causal, mask, q.shape[1]) is True:
        causal, mask, mask_operands = True, None, ()
    mesh = _mesh_or_none()
    # heads split over the model axis only in whole groups
    per_shard = _per_shard_spec(mesh, q.shape[0], k.shape[2])
    impl = _resolve_impl(impl, q, mesh, initializing, per_shard is not None)
    widened = {} if scale is None else {"scale": scale}
    if mask is not None:
        widened["mask"] = mask
    if mask_operands:
        widened["mask_operands"] = tuple(mask_operands)
    if impl in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(
                f"attn_impl={impl!r} needs an initialized runtime mesh"
            )
        if widened or v.shape != q.shape:
            raise ValueError(
                f"attn_impl={impl!r} takes one head width, the default "
                "scale, a causal or full mask and ungrouped heads; latent "
                "and grouped attention and mask rules run full or blockwise"
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
                and num_heads % mesh.shape[MODEL_AXIS] == 0
            ) else None
        spec = P((DATA_AXIS, FSDP_AXIS), SEQUENCE_AXIS, head_axis, None)
        return shard_map(
            lambda q, k, v: local_fn(q, k, v, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    if impl == "blockwise":
        # a kernel is a custom call GSPMD cannot split: on a mesh it runs
        # per shard, where each device holds whole rows and heads; the
        # scan schedule is plain XLA and shards as it stands
        kernels = _blockwise.engage_kernels(
            q, shardable=per_shard is not None) is not None
        if on_tiles is not None and mask is not None:
            on_tiles(*_blockwise.tile_counts(mask, q.shape[1], kernels=kernels))
        if kernels and per_shard is not None:
            return _blockwise_per_shard(
                q, k, v, *mask_operands, mesh=mesh, spec=per_shard, causal=causal,
                scale=scale, mask=mask)
        return _blockwise.blockwise_attention(q, k, v, causal=causal, **widened)
    if impl == "full":
        return attention_reference(q, k, v, causal=causal, **widened)
    raise ValueError(
        f"unknown attn_impl {impl!r}; known: auto, full, ring, "
        "ulysses, blockwise"
    )


class SelfAttention(nn.Module):
    """Causal multi-head self-attention with ring/full dispatch."""

    num_heads: int
    head_dim: int
    causal: bool = True
    #: "auto" picks ring attention when the mesh shards the sequence axis
    #: (no head-count constraint), blockwise where its flash kernels run
    #: (_FLASH_AUTO_LEN+ tokens on a TPU) and for unsharded sequences of
    #: _BLOCKWISE_AUTO_LEN+ tokens, full otherwise; "ulysses" opts into
    #: the all-to-all form
    #: (tpuframe.ops.ulysses — one re-shard instead of N-1 ppermute hops,
    #: needs num_heads divisible by the seq-axis size); "blockwise" is the
    #: single-shard flash-style O(L*block) path
    #: (tpuframe.ops.blockwise_attention) for long context on one chip.
    attn_impl: str = "auto"  # "auto" | "full" | "ring" | "ulysses" | "blockwise"
    dtype: Any = jnp.float32
    #: key/value heads, one a group of ``num_heads / num_kv_heads`` query
    #: heads (0: as many as query heads)
    num_kv_heads: int = 0
    #: RMSNorm with a learned scale on every query head and key head
    qk_norm: bool = False
    norm_eps: float = 1e-6
    #: a rule on positions in ``causal``'s place (`ops.ring_attention`'s
    #: protocol: `BlockDiffusionMask`, `SlidingWindowMask`)
    mask: Any = None
    #: an output gate: the query projection twice as wide, ``[q | gate]``
    #: (all heads' queries, then all heads' gates), and ``sigmoid(gate)`` on
    #: the attention output before ``attn_out``
    gated: bool = False
    #: the head norms in the ``(1 + scale)`` form
    norm_unit_offset: bool = False
    #: a learned index that chooses the keys a query sees (DeepSeek sparse
    #: attention; `_chosen_keys`), as (name, value) pairs: ``num_heads`` and
    #: ``head_dim`` of the index, ``topk`` keys a query
    sparse_index: tuple = ()

    def _chosen_keys(self, x):
        """(rule, operands) of attention over the keys the index chooses, or
        (None, ()) where the row is no longer than ``topk`` and the layer is
        plain causal attention (the index then does not run).

        Index queries ``qI = x W_q`` (``num_heads`` of ``head_dim``), one index
        key head ``kI = LayerNorm(x W_k)`` (scale and bias), head weights ``w =
        x W_w`` in float32; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
        and the ``topk`` largest a query among the keys not after it
        (`ops.sparse_index`), one choice for all heads.  No rotary turn inside
        the index.  The choice is discrete: no gradient of the objective
        reaches the index, which runs forward only, under ``stop_gradient``;
        its four leaves are frozen leaves of the step."""
        index = dict(self.sparse_index)
        heads, width = index["num_heads"], index["head_dim"]
        rule = SelectedKeysMask(index["topk"])
        b, l, _ = x.shape
        causal_pairs = b * l * (l + 1) // 2

        def count(selected):
            for name, value in (("selected", selected), ("causal", causal_pairs)):
                self.sow("counters", f"attention/pairs_{name}", jnp.float32(value),
                         reduce_fn=lambda a, b: b, init_fn=lambda: jnp.float32(0))

        plain = rule.plain(l)
        if plain and not self.is_initializing():
            count(causal_pairs)
            return None, ()
        with jax.named_scope("tpuframe/attn/index"):
            x = jax.lax.stop_gradient(x)
            dense = lambda name, n, dtype: nn.Dense(  # noqa: E731
                n, use_bias=False, dtype=dtype, name=name)
            qi = dense("index_q", heads * width, self.dtype)(x).reshape(b, l, heads, width)
            ki = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name="index_k_norm")(
                dense("index_k", width, self.dtype)(x))
            w = dense("index_w", heads, jnp.float32)(x)
            if plain:
                return None, ()
            qi, ki, w = jax.lax.stop_gradient((qi, ki, w))
            if self.is_initializing():
                # init's sample batch need not divide the mesh
                chosen, counts = select_keys_reference(qi, ki, w, rule.topk)
            else:
                chosen, counts = select_keys(qi, ki, w, rule.topk, mesh=_mesh_or_none())
                count(jnp.sum(counts))
        return rule, (chosen,)

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False, rope=None) -> jax.Array:
        """``rope``: (cos, sin) tables at the rows' position ids, turned
        into every query and key head over the tables' width (a head's
        first dimensions where they are narrower than the head)."""
        features = self.num_heads * self.head_dim
        kv_heads = self.num_kv_heads or self.num_heads
        dense = lambda name, heads: nn.Dense(  # noqa: E731
            heads * self.head_dim, use_bias=False, dtype=self.dtype, name=name
        )
        b, l, _ = x.shape
        # head norms before rotary positions: one op on the projection's rows
        fused = self.qk_norm and rope is not None
        gate = None

        def project(name, heads, norm=None):
            nonlocal gate
            if self.gated and name == "query":
                y = dense(name, 2 * heads)(x)
                y, gate = y[..., :features], y[..., features:]
            else:
                y = dense(name, heads)(x)
            if fused and norm:
                y = HeadNormRope(heads, self.norm_eps, self.norm_unit_offset,
                                 name=norm)(y, rope)
            return y.reshape(b, l, heads, self.head_dim)

        q = project("query", self.num_heads, "q_norm")
        k = project("key", kv_heads, "k_norm")
        v = project("value", kv_heads)
        if self.qk_norm and not fused:
            q = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        unit_offset=self.norm_unit_offset, name="q_norm")(q)
            k = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        unit_offset=self.norm_unit_offset, name="k_norm")(k)
        elif rope is not None and not fused:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)

        def count_tiles(visited, needed):
            # static numbers a head and a row, times the rows and heads:
            # counted here on the host, once a trace, where their reader
            # takes the ratio; nothing of them rides the step
            registry = get_telemetry().registry
            for name, value in (("visited", visited), ("needed", needed)):
                registry.counter(f"attention/tiles_{name}").inc(
                    value * b * self.num_heads)

        mask, operands = self.mask, {}
        if self.sparse_index:
            if mask is not None or not self.causal:
                raise ValueError("an index chooses among the keys not after a query: "
                                 "sparse_index takes causal attention and no other mask rule")
            mask, chosen = self._chosen_keys(x)
            operands = {"mask_operands": chosen} if chosen else {}
        # a rule that names its kernels names its layers' scope too
        # (``tpuframe/attn/window``)
        scope = "tpuframe/attn" + getattr(mask, "suffix", "").replace("_", "/")
        with jax.named_scope(scope):
            out = _attend(
                q, k, v, impl=self.attn_impl, causal=self.causal,
                num_heads=self.num_heads, initializing=self.is_initializing(),
                mask=mask, on_tiles=count_tiles, **operands,
            )
        out = out.reshape(b, l, features)
        if gate is not None:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return nn.Dense(
            x.shape[-1], use_bias=False, dtype=self.dtype, name="attn_out"
        )(out)


class GatedMLP(nn.Module):
    """``(silu(x W_gate) * (x W_in)) W_out``, no biases."""

    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name
        )
        h = nn.silu(dense(self.hidden, "gate")(x)) * dense(self.hidden, "in")(x)
        return dense(x.shape[-1], "out")(h)


class ShortConv(nn.Module):
    """LFM2's double-gated short convolution, a mixer that is no
    attention: ``[B | C | h] = x W_in``, a ``taps``-tap causal depthwise
    convolution of ``B * h`` along the sequence, gated by ``C``, then
    ``W_out``; no bias, no activation (`tpuframe.ops.short_conv`)."""

    taps: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name
        )
        with jax.named_scope("tpuframe/shortconv"):
            bch = dense(3 * d, "in_proj")(x)
            w = self.param("w", nn.initializers.lecun_normal(), (self.taps, d))
            if self.is_initializing():
                # init's sample batch need not divide the mesh
                y = short_conv_reference(bch, w)
            else:
                y = short_conv(bch, w, mesh=_mesh_or_none())
            return dense(d, "out_proj")(y)


def _a_log_init(key, shape, dtype=jnp.float32):
    """Gated DeltaNet's decay rates: ``log(uniform(0, 16))``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, minval=1e-3, maxval=16.0))


def _deltanet_decay(ba, a_log, dt_bias, hv):
    """``g`` and ``beta`` a position and value head from the ``[b | a]``
    projection's float32 output: a few small arrays, computed again in the
    backward pass (`jax.checkpoint`)."""
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + dt_bias.astype(jnp.float32))
    return g, beta


def _deltanet_gate(o, qkvz, scale, eps, dtype):
    """The gated norm on the rule's output: ``o / rms(o) * scale * silu(z)``
    a head, float32 inside; computed again in the backward pass too.  ``z``
    is the last columns of the fused projection's output, sliced here and
    not before: what a layer keeps for its backward pass is then that output
    itself, once, which `conv_silu` keeps too, and no copy of a part of it.
    Written on views that split the sequence into a tile's 8 positions: on
    plain (B, L, heads, width) views XLA converts ``z`` to float32 and copies
    that to ``o``'s layout, a head's rows together, each way (AOT compiles,
    PR 45); on these it reads ``z``'s tiles where they lie."""
    b, l, hv, dv = o.shape
    tiles = (b, l // 8, 8, hv, dv) if l % 8 == 0 else o.shape
    z = qkvz[..., qkvz.shape[-1] - hv * dv:].reshape(tiles)
    o32 = o.reshape(tiles).astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    y = (o32 * scale.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))).astype(dtype)
    return y.reshape(o.shape)


class GatedDeltaNet(nn.Module):
    """Qwen3-Next's linear-attention mixer: the gated delta rule
    (`tpuframe.ops.gated_delta`) between fused projections.  ``[q | k | v |
    z] = x W_qkvz`` (the columns in that order, the heads of each side by
    side) and ``[b | a] = x W_ba``; ``[q | k | v]`` goes through a
    ``conv_taps``-tap causal depthwise convolution and SiLU; queries and
    keys are L2-normalised over their width, the queries scaled by
    ``key_dim^-1/2``; ``beta = sigmoid(b)`` and ``g = -exp(A_log) *
    softplus(a + dt_bias)`` a value head, in float32; a key head serves
    ``num_value_heads / num_key_heads`` value heads in a row; the rule's
    output is RMS-normalised a head with a learned scale, gated by
    ``silu(z)``, and projected by ``W_out``.  No bias anywhere."""

    num_key_heads: int
    num_value_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int = 4
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, l, d = x.shape
        hk, hv, dk, dv = self.num_key_heads, self.num_value_heads, self.key_dim, self.value_dim
        keys, values = hk * dk, hv * dv
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name
        )
        with jax.named_scope("tpuframe/deltanet"):
            qkvz = dense(2 * keys + 2 * values, "in_proj_qkvz")(x)
            ba = dense(2 * hv, "in_proj_ba")(x).astype(jnp.float32)
            w = self.param("conv", nn.initializers.lecun_normal(),
                           (self.conv_taps, 2 * keys + values))
            a_log = self.param("A_log", _a_log_init, (hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
            scale = self.param("norm", nn.initializers.ones, (dv,))
            # the convolution, SiLU and unit norms of [q | k | v], read in
            # place from the fused output; init's sample batch need not
            # divide the mesh
            inputs = conv_silu_reference if self.is_initializing() else functools.partial(
                conv_silu, mesh=_mesh_or_none())
            q, k, v = inputs(qkvz, w, key_heads=hk, key_dim=dk)
            q, k = q.reshape(b, l, hk, dk), k.reshape(b, l, hk, dk)
            v = v.reshape(b, l, hv, dv)
            g, beta = jax.checkpoint(_deltanet_decay, static_argnums=(3,))(
                ba, a_log, dt_bias, hv)
            with jax.named_scope("tpuframe/deltanet/rule"):
                if self.is_initializing():
                    # init's sample batch need not divide the mesh
                    o = gated_delta_reference(q, k, v, g, beta)
                else:
                    o = gated_delta(q, k, v, g, beta, mesh=_mesh_or_none())
                    # static numbers a call, counted here on the host, once a
                    # trace, where their reader divides them
                    registry = get_telemetry().registry
                    registry.counter("deltanet/chunks").inc(chunks_walked(b, l, hv))
                    registry.counter("deltanet/calls").inc(1)
            y = jax.checkpoint(_deltanet_gate, static_argnums=(3, 4))(
                o, qkvz, scale, self.norm_eps, self.dtype)
            return dense(d, "out_proj")(y.reshape(b, l, values))


def _kda_decay(fb, b, a_log, dt_bias, heads):
    """``g`` a position, head and key channel and ``beta`` a position and
    head, from the decay pair's float32 output and beta's projection:
    computed again in the backward pass (`jax.checkpoint`)."""
    lead = fb.shape[:-1]
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
        fb + dt_bias.astype(jnp.float32)).reshape(lead + (heads, -1))
    return g, jax.nn.sigmoid(b.astype(jnp.float32))


def _kda_gate(o, gate, scale, eps, dtype):
    """The gated norm on the rule's output: ``o / rms(o) * scale *
    sigmoid(gate)`` a head, float32 inside; computed again in the backward
    pass.  On views that split the sequence into a tile's 8 positions, as
    `_deltanet_gate`'s and for its reason."""
    b, l, h, dv = o.shape
    tiles = (b, l // 8, 8, h, dv) if l % 8 == 0 else o.shape
    o32 = o.reshape(tiles).astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    y = o32 * scale.astype(jnp.float32) * jax.nn.sigmoid(gate.reshape(tiles).astype(jnp.float32))
    return y.astype(dtype).reshape(o.shape)


class KimiDeltaAttention(nn.Module):
    """Kimi-Linear's linear-attention mixer (KDA): the delta rule whose decay
    is a vector a head (`tpuframe.ops.kda`).  ``[q | k | v] = x W_qkv``
    (``num_heads`` heads of ``head_dim`` each, the heads of each side by
    side) through a ``conv_taps``-tap causal depthwise convolution and SiLU,
    queries and keys L2-normalised over a head, the queries scaled by
    ``head_dim^-1/2`` (`ops.short_conv.conv_silu`, on the model's rows);
    ``g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)`` a position, head
    and key channel (``A_log`` a head, ``dt_bias`` a channel), float32 from
    the second product's accumulator on; ``beta = sigmoid(x W_b)`` a head;
    the rule's output is RMS-normalised a head with a learned scale, gated by
    ``sigmoid((x W_ga) W_gb)`` (a sigmoid, where `GatedDeltaNet` has SiLU),
    and projected by ``W_out``.  The two low-rank pairs are ``rank`` wide (0:
    ``head_dim``).  No bias anywhere."""

    num_heads: int
    head_dim: int
    conv_taps: int = 4
    rank: int = 0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, l, d = x.shape
        h, dk = self.num_heads, self.head_dim
        width, rank = h * dk, self.rank or self.head_dim
        dense = lambda n, name, **kw: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, **kw
        )
        with jax.named_scope("tpuframe/kda"):
            qkv = dense(3 * width, "in_proj_qkv")(x)
            w = self.param("conv", nn.initializers.lecun_normal(), (self.conv_taps, 3 * width))
            a_log = self.param("A_log", _a_log_init, (h,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (width,))
            scale = self.param("norm", nn.initializers.ones, (dk,))
            # init's sample batch need not divide the mesh
            inputs = conv_silu_reference if self.is_initializing() else functools.partial(
                conv_silu, mesh=_mesh_or_none())
            q, k, v = (a.reshape(b, l, h, dk) for a in inputs(qkv, w, key_heads=h, key_dim=dk))
            with jax.named_scope("tpuframe/kda/decay"):
                # the decay's second product keeps its float32 sums: g is
                # summed along a chunk before any exponential
                fb = dense(width, "f_b", dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32))(
                        dense(rank, "f_a")(x))
                g, beta = jax.checkpoint(_kda_decay, static_argnums=(4,))(
                    fb, dense(h, "b_proj")(x), a_log, dt_bias, h)
            with jax.named_scope("tpuframe/kda/rule"):
                if self.is_initializing():
                    # init's sample batch need not divide the mesh
                    o = kda_reference(q, k, v, g, beta)
                else:
                    o = kda(q, k, v, g, beta, mesh=_mesh_or_none())
                    # static numbers a call, counted here on the host, once a
                    # trace, where their reader divides them
                    registry = get_telemetry().registry
                    registry.counter("kda/chunks").inc(chunks_walked(b, l, h))
                    registry.counter("kda/calls").inc(1)
            with jax.named_scope("tpuframe/kda/gate"):
                gate = dense(width, "g_b")(dense(rank, "g_a")(x))
                y = jax.checkpoint(_kda_gate, static_argnums=(3, 4))(
                    o, gate, scale, self.norm_eps, self.dtype)
            return dense(d, "out_proj")(y.reshape(b, l, width))


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) without a query latent.

    Keys and values come from one ``kv_lora_rank``-wide latent per token:
    ``x W_kva -> [c | k_rope]``, ``RMSNorm(c) W_kvb ->`` per head
    ``[k_nope | v]``.  Rotary positions turn ``q_rope`` of every head and
    the one ``k_rope`` all heads share; ``rope=None`` means no positions:
    nothing is turned, and ``k_rope`` is only the part of a key that all
    heads share (Kimi-Linear's ``mla_use_nope``).  Queries and keys are
    ``head_dim + rope_dim`` wide, values ``v_head_dim``, and the softmax
    scale is the caller's (YaRN's temperature folded in).  The core is
    :func:`_attend`, ``full`` or ``blockwise``.
    """

    num_heads: int
    head_dim: int        # the part of a query/key head without position
    rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    scale: float
    norm_eps: float = 1e-6
    causal: bool = True
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, rope=None, train: bool = False) -> jax.Array:
        h, dn, dr, dv = self.num_heads, self.head_dim, self.rope_dim, self.v_head_dim
        b, l, _ = x.shape
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name
        )
        with jax.named_scope("tpuframe/mla"):
            q = dense(h * (dn + dr), "query")(x).reshape(b, l, h, dn + dr)
            kva = dense(self.kv_lora_rank + dr, "kv_a")(x)
            c = RMSNorm(eps=self.norm_eps, dtype=self.dtype, name="kv_norm")(
                kva[..., :self.kv_lora_rank]
            )
            kvb = dense(h * (dn + dv), "kv_b")(c).reshape(b, l, h, dn + dv)
            k_rope = kva[..., None, self.kv_lora_rank:]
            if rope is not None:
                k_rope = apply_rope(k_rope, *rope)
                q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], *rope)], axis=-1)
            k = jnp.concatenate(
                [kvb[..., :dn], jnp.broadcast_to(k_rope, (b, l, h, dr))], axis=-1
            )
            out = _attend(
                q, k, kvb[..., dn:], impl=self.attn_impl, causal=self.causal,
                num_heads=h, initializing=self.is_initializing(),
                scale=self.scale,
            )
            return dense(x.shape[-1], "attn_out")(out.reshape(b, l, h * dv))


class Block(nn.Module):
    """Pre-norm transformer block: norm -> attn -> +res, norm -> FFN -> +res.

    ``moe_experts > 0`` replaces the dense MLP with the expert layer
    (:class:`tpuframe.models.moe.MoEMLP`): expert weights shard over the
    ``expert`` mesh axis via ``moe_rules`` and the router's balance loss
    rides the ``aux_loss`` collection into the train objective.
    ``norm="rms"``, ``kv_lora_rank > 0`` (latent attention in the
    ``"full_attention"`` layers, rotary positions given as ``rope``) and
    ``mlp_gated`` (SiLU-gated MLP, no bias) are the other kinds of layer;
    the defaults are GPT-2's.  ``rope=None`` means no positions: no
    attention layer of the block turns anything.
    ``mixer="conv"`` puts the short-convolution operator
    (:class:`ShortConv`) in attention's place; ``"sliding_attention"``
    is multi-head attention under a causal band of ``sliding_window``
    keys (`ops.ring_attention.SlidingWindowMask`), its parameters a
    ``"full_attention"`` layer's leaf for leaf; ``"linear_attention"`` is
    the gated delta rule's mixer (:class:`GatedDeltaNet`), its sizes in
    ``linear_attention``; ``"kda"`` the delta rule whose decay is a vector a
    head (:class:`KimiDeltaAttention`), its sizes in ``kda``.
    """

    #: the mixers a layer can have
    MIXERS = ("full_attention", "sliding_attention", "conv", "linear_attention", "kda")

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
    norm: str = "layer"  # "layer" | "rms"
    norm_eps: float = 1e-6
    #: > 0: latent attention with this latent width; ``head_dim`` is then
    #: the position-free part of a query/key head
    kv_lora_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0
    attn_scale: float | None = None
    #: 0: ``d_model * mlp_ratio``
    mlp_dim: int = 0
    mlp_gated: bool = False
    #: further arguments of the expert layer, as a tuple of (name, value)
    moe_kwargs: tuple = ()
    #: multi-head attention's grouped heads, head norms and mask rule
    #: (`SelfAttention`); its rotary positions arrive as ``rope``
    num_kv_heads: int = 0
    qk_norm: bool = False
    mask: Any = None
    mixer: str = "full_attention"  # one of ``MIXERS``
    conv_taps: int = 3
    sliding_window: int = 0
    #: multi-head attention's output gate (`SelfAttention.gated`)
    attn_gated: bool = False
    #: every RMSNorm of the block, the head norms too, in the ``(1 + scale)`` form
    norm_unit_offset: bool = False
    #: the sizes of a ``"linear_attention"`` layer (`GatedDeltaNet`'s
    #: arguments), as a tuple of (name, value)
    linear_attention: tuple = ()
    #: multi-head attention's index of chosen keys (`SelfAttention.sparse_index`)
    sparse_index: tuple = ()
    #: the sizes of a ``"kda"`` layer (`KimiDeltaAttention`'s arguments), as
    #: a tuple of (name, value)
    kda: tuple = ()

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False, rope=None) -> jax.Array:
        d = x.shape[-1]
        if self.norm == "rms":
            ln = lambda name: RMSNorm(  # noqa: E731
                eps=self.norm_eps, dtype=self.dtype,
                unit_offset=self.norm_unit_offset, name=name
            )
        else:
            ln = lambda name: FusedLayerNorm(  # noqa: E731
                dtype=self.dtype, use_mesh=self.ln_use_mesh, name=name
            )
        y = ln("ln1")(x)
        if self.mixer == "conv":
            y = ShortConv(self.conv_taps, dtype=self.dtype, name="conv")(y)
        elif self.mixer == "linear_attention":
            if not self.linear_attention:
                raise ValueError("a linear_attention layer takes its sizes from "
                                 "linear_attention (num_key_heads, num_value_heads, "
                                 "key_dim, value_dim)")
            y = GatedDeltaNet(norm_eps=self.norm_eps, dtype=self.dtype, name="deltanet",
                              **dict(self.linear_attention))(y)
        elif self.mixer == "kda":
            if not self.kda:
                raise ValueError("a kda layer takes its sizes from kda (num_heads, head_dim)")
            y = KimiDeltaAttention(norm_eps=self.norm_eps, dtype=self.dtype, name="kda",
                                   **dict(self.kda))(y)
        elif self.mixer not in self.MIXERS:
            raise ValueError(f"unknown mixer {self.mixer!r}; known: "
                             + ", ".join(self.MIXERS))
        elif self.kv_lora_rank:
            if self.mixer != "full_attention":
                raise ValueError("latent attention takes no window")
            y = LatentAttention(
                self.num_heads, self.head_dim, self.rope_dim, self.v_head_dim,
                self.kv_lora_rank, scale=self.attn_scale,
                norm_eps=self.norm_eps, causal=self.causal,
                attn_impl=self.attn_impl, dtype=self.dtype, name="attn",
            )(y, rope, train=train)
        else:
            mask = self.mask
            if self.mixer == "sliding_attention":
                mask = SlidingWindowMask(self.sliding_window)
            y = SelfAttention(
                self.num_heads, self.head_dim, causal=self.causal,
                attn_impl=self.attn_impl, dtype=self.dtype,
                num_kv_heads=self.num_kv_heads, qk_norm=self.qk_norm,
                norm_eps=self.norm_eps, mask=mask, gated=self.attn_gated,
                norm_unit_offset=self.norm_unit_offset, name="attn",
                **({"sparse_index": self.sparse_index} if self.sparse_index else {}),
            )(y, train=train, rope=rope)
        if self.dropout:
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = ln("ln2")(x)
        if self.moe_experts:
            from tpuframe.models.moe import MoEMLP

            y = MoEMLP(
                num_experts=self.moe_experts, top_k=self.moe_top_k,
                mlp_ratio=self.mlp_ratio, dtype=self.dtype, name="moe",
                **dict(self.moe_kwargs),
            )(y, train=train)
        elif self.mlp_gated:
            y = GatedMLP(self.mlp_dim or d * self.mlp_ratio, dtype=self.dtype,
                         name="mlp")(y)
        else:
            y = nn.Dense(
                self.mlp_dim or d * self.mlp_ratio, dtype=self.dtype,
                name="mlp_in",
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

    The defaults build GPT-2's kind of layer (learned positions,
    LayerNorm, multi-head attention, a GELU MLP).  The other kinds are
    switched on by their sizes: ``norm="rms"``; ``rope_dim > 0`` rotary
    positions (with a config's YaRN ``rope_scaling``) in place of the
    position table; ``kv_lora_rank > 0`` latent attention, its heads
    ``head_dim + rope_dim`` wide for queries and keys and ``v_head_dim``
    for values (without it the rotary positions turn multi-head
    attention's whole heads, ``rope_dim == head_dim``); ``num_kv_heads``
    grouped heads and ``qk_norm`` an RMSNorm on every query and key
    head; ``mlp_gated`` a SiLU-gated MLP of width ``mlp_dim``;
    ``moe_experts > 0`` the expert layer in every block from
    ``moe_first_dense`` on, with the dense MLP before it;
    ``layer_types`` a mixer for each layer, as a config publishes them:
    ``"full_attention"`` (the attention the other sizes describe),
    ``"sliding_attention"`` (the same under a causal band of
    ``sliding_window`` keys), ``"conv"`` (the short-convolution
    operator of ``conv_taps`` taps) or ``"linear_attention"`` (the gated
    delta rule, its sizes in ``linear_attention``: ``num_key_heads``,
    ``num_value_heads``, ``key_dim``, ``value_dim``, ``conv_taps``) or
    ``"kda"`` (the delta rule with a vector decay, its sizes in ``kda``:
    ``num_heads``, ``head_dim``, ``conv_taps``; with ``kv_lora_rank`` the
    ``"full_attention"`` layers of such a pattern are latent attention);
    ``nope`` no position encoding anywhere (a config's ``mla_use_nope``): no
    rotary tables and no position table are built, and ``rope_dim`` is only
    the width of the key part all heads of latent attention share;
    ``attn_gated`` an output gate on multi-head attention; ``rope_dim``
    under ``head_dim`` turns the first ``rope_dim`` dimensions of every
    head (a config's ``partial_rotary_factor``); ``sparse_index`` a learned
    index that chooses the ``topk`` keys a query of a ``"full_attention"``
    layer sees (``num_heads``, ``head_dim``, ``topk``; `SelfAttention`);
    ``norm_unit_offset``
    every RMSNorm in the ``(1 + scale)`` form; ``rope_parameters`` rotary
    parameters by kind of attention layer, as a config publishes them
    (``{"full_attention": {"rope_type": "yarn", "rope_theta": ..,
    "factor": .., ...}, "sliding_attention": {"rope_type": "default",
    "rope_theta": ..}}``): each kind that occurs gets tables of its own,
    and a kind without an entry ``rope_theta`` / ``rope_scaling``.

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
    #: 0: ``num_heads * head_dim``
    d_model: int = 0
    norm: str = "layer"
    norm_eps: float = 1e-6
    rope_dim: int = 0
    rope_theta: float = 10000.0
    #: a config's ``rope_scaling`` (YaRN): a dict, kept as sorted items
    rope_scaling: Any = None
    kv_lora_rank: int = 0
    v_head_dim: int = 0
    mlp_dim: int = 0
    mlp_gated: bool = False
    #: blocks before this one keep the dense MLP
    moe_first_dense: int = 0
    #: further arguments of the expert layer (``MoEMLP``): a dict, kept
    #: as sorted items
    moe_kwargs: Any = ()
    #: 0: ``num_heads`` (multi-head attention)
    num_kv_heads: int = 0
    qk_norm: bool = False
    #: one mixer a layer, of ``Block.MIXERS`` (empty: attention in every
    #: layer); a list, kept as a tuple
    layer_types: Any = ()
    conv_taps: int = 3
    #: keys a ``"sliding_attention"`` layer's query sees, its own among them
    sliding_window: int = 0
    #: rotary parameters by kind of attention layer: a dict of dicts, kept
    #: as sorted items
    rope_parameters: Any = None
    attn_gated: bool = False
    norm_unit_offset: bool = False
    #: a ``"linear_attention"`` layer's sizes: a dict, kept as sorted items
    linear_attention: Any = ()
    #: a learned index that chooses ``topk`` keys a query in every
    #: ``"full_attention"`` layer (``num_heads``, ``head_dim``, ``topk``: a
    #: config's ``sa_config``): a dict, kept as sorted items
    sparse_index: Any = ()
    #: a ``"kda"`` layer's sizes: a dict, kept as sorted items
    kda: Any = ()
    #: no position encoding anywhere: nothing is turned, no table is built
    nope: bool = False

    def __post_init__(self):
        # module attributes are hashed with the train state's treedef:
        # what a JSON config hands over as dict or list becomes tuples
        def frozen(v):
            if isinstance(v, dict):
                return tuple(sorted((k, frozen(x)) for k, x in v.items()))
            return tuple(frozen(x) for x in v) if isinstance(v, list) else v

        for name in ("rope_scaling", "moe_kwargs", "layer_types", "rope_parameters",
                     "linear_attention", "sparse_index", "kda"):
            object.__setattr__(self, name, frozen(getattr(self, name)))
        super().__post_init__()

    def attn_scale(self) -> float | None:
        """Latent attention's softmax scale: ``width^-0.5`` times the
        square of YaRN's temperature over all dimensions."""
        if not self.kv_lora_rank:
            return None
        scaling = dict(self.rope_scaling or ())
        m = yarn_mscale(scaling.get("factor", 1.0), scaling.get("mscale_all_dim", 0.0))
        return (self.head_dim + self.rope_dim) ** -0.5 * m * m

    def _rope_of(self, kind: str) -> tuple[float, dict | None]:
        """(theta, YaRN scaling or None) of the rotary tables of a kind of
        attention layer: its entry in ``rope_parameters``, or the model's
        ``rope_theta`` / ``rope_scaling`` where it has none."""
        entry = dict(dict(self.rope_parameters or ()).get(kind, ()))
        if not entry:
            return self.rope_theta, dict(self.rope_scaling or ()) or None
        return (entry.get("rope_theta", self.rope_theta),
                entry if entry.get("rope_type") == "yarn" else None)

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False) -> jax.Array:
        return self._decode(tokens, train)

    def _decode(self, tokens, train, *, positions=None, mask=None, head_len=None):
        """Embedding, blocks, final norm and head over (B, L) tokens.  A
        subclass that builds its own rows hands over what they need:
        ``positions`` (static position ids where they are not ``0 .. L - 1``),
        a ``mask`` rule for every block's attention in place of causal
        (a model whose layers bring rules of their own takes none), and
        ``head_len`` (logits for the first so many positions only)."""
        d_model = self.d_model or self.num_heads * self.head_dim
        x = nn.Embed(self.vocab_size, d_model, dtype=self.dtype, name="embed")(tokens)
        mixers = self.layer_types or ("full_attention",) * self.num_layers
        if len(mixers) != self.num_layers:
            raise ValueError(f"layer_types names {len(mixers)} layers of "
                             f"{self.num_layers}")
        if mask is not None and "sliding_attention" in mixers:
            raise ValueError("a mask rule for every block and window layers, which "
                             "bring their own, do not go together")
        ropes = {}
        if self.rope_dim and not self.nope:
            if not self.kv_lora_rank and (self.rope_dim > self.head_dim or self.rope_dim % 2):
                raise ValueError("multi-head attention turns a head's first dimensions "
                                 f"in pairs: rope_dim {self.rope_dim} is odd or over "
                                 f"head_dim {self.head_dim}")
            # one pair of tables a kind of attention layer that occurs
            ropes = {kind: rope_tables(tokens.shape[1], self.rope_dim,
                                       *self._rope_of(kind), positions)
                     for kind in dict.fromkeys(mixers)
                     if kind not in ("conv", "linear_attention", "kda")}
        elif not self.nope:
            pos = nn.Embed(self.max_len, d_model, dtype=self.dtype, name="pos_embed")(
                jnp.arange(tokens.shape[1])[None, :]
            )
            x = x + pos
        block_cls = RematBlock if self.remat else Block
        for i in range(self.num_layers):
            sparse = self.moe_experts and i >= self.moe_first_dense
            x = block_cls(
                self.num_heads, self.head_dim, mlp_ratio=self.mlp_ratio,
                dropout=self.dropout, causal=True, attn_impl=self.attn_impl,
                dtype=self.dtype, moe_experts=self.moe_experts if sparse else 0,
                moe_top_k=self.moe_top_k, norm=self.norm,
                norm_eps=self.norm_eps, kv_lora_rank=self.kv_lora_rank,
                rope_dim=self.rope_dim, v_head_dim=self.v_head_dim,
                attn_scale=self.attn_scale(), mlp_dim=self.mlp_dim,
                mlp_gated=self.mlp_gated, moe_kwargs=self.moe_kwargs,
                num_kv_heads=self.num_kv_heads, qk_norm=self.qk_norm, mask=mask,
                mixer=mixers[i], conv_taps=self.conv_taps,
                sliding_window=self.sliding_window, attn_gated=self.attn_gated,
                norm_unit_offset=self.norm_unit_offset,
                linear_attention=self.linear_attention, name=f"block{i}",
                **({"kda": self.kda} if mixers[i] == "kda" else {}),
                **({"sparse_index": self.sparse_index}
                   if self.sparse_index and mixers[i] == "full_attention" else {}),
            )(x, train, ropes.get(mixers[i]))
        if head_len is not None:
            x = x[:, :head_len]
        if self.norm == "rms":
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        unit_offset=self.norm_unit_offset, name="ln_f")(x)
        else:
            x = FusedLayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head"
        )(x)
        return logits.astype(jnp.float32)
