"""Pallas TPU kernels for tpuframe's hot ops.

The reference rides on native CUDA kernels it never sees — cuDNN convs
behind torchvision modules, DeepSpeed's fused Adam, NCCL collectives
(SURVEY.md §2.3).  tpuframe's equivalents: XLA compiles the convs and
collectives; this package hand-writes the remaining hot spots as Pallas
kernels, each with a jnp reference implementation that is both the CPU
fallback and the correctness oracle for tests.

- :func:`normalize_images` — uint8→float, scale, per-channel mean/std
  normalize in one pass (replaces torchvision's ToTensor+Normalize
  chain, `/root/reference/utils/hf_dataset_utilities.py:58-81`): plain
  jnp on the batch in its own layout, one XLA fusion.
- :func:`fused_cross_entropy` — softmax cross entropy with a custom VJP
  that recomputes the softmax in the backward kernel instead of
  materializing it in HBM.
- :func:`grouped_matmul` — rows sorted by group times one weight a
  group, the expert product of the no-drop mixture-of-experts layer:
  three kernels driven by the group sizes (the product and both
  gradients' products), ``jax.lax.ragged_dot`` where they do not run.
- :func:`unsort` — the same layer's un-sort: every token's routed rows
  summed out of the sorted slots, the routed rows read once and each
  token's row written once (XLA's form gathers a row a (token, choice)
  pair and reduces them).
- :func:`short_conv` — LFM2's double-gated short convolution between
  its two projections (gate, a few causal depthwise taps along the
  sequence, gate) in one pass each way.
- :func:`conv_silu` — what Qwen3-Next's gated delta rule reads, the
  second entry of the same module: causal depthwise taps over the
  ``[q | k | v]`` columns of a fused projection's output, SiLU, and unit
  queries and keys a head, in one pass each way.
- :func:`head_norm_rope` — an RMSNorm on every head of a query or key
  projection's rows and the rotary turn behind it, in one pass each way
  (XLA makes of the two a handful of float32 passes over a
  (B, L, H, D) view).
- :func:`gated_delta` — the gated delta rule (Gated DeltaNet's linear
  attention): a recurrence with a (d_k, d_v) state a head, run in chunks;
  the pass that carries the state over the chunks is a kernel pair, the
  state resident in VMEM.
- :func:`kda` — Kimi Delta Attention's rule: the same recurrence with a
  decay a key channel (a vector a head, scaling the state's rows); its
  pass over the chunks is a kernel pair of its own, the chunk-local part
  XLA's batched products.
- :func:`select_keys` — the learned index of sparse attention: index
  scores of a tile of queries held in VMEM and the exact choice of
  ``topk`` keys a query by bisection, one byte a (query, key) pair out
  (what `SelectedKeysMask` reads in the attention schedules).
- :func:`quant_encode` / :func:`quant_decode` — the compressed gradient
  wire's amax/scale/round/pack stages in one VMEM pass each
  (``parallel.compression`` calls them for the bucketed transport).

Exports are lazy (PEP 562, like ``tpuframe.parallel``): resolving a
name off this package must not import jax, so the knob registries and
the doctor can enumerate op modules from wedged-backend or jax-less
processes — importing a *resolved* symbol still pulls in the real
kernel module.
"""

# tpuframe-lint: stdlib-only

import sys as _sys
import types as _types

_LAZY = {
    "use_pallas": "tpuframe.ops.dispatch",
    "grouped_matmul": "tpuframe.ops.grouped_matmul",
    "grouped_matmul_reference": "tpuframe.ops.grouped_matmul",
    "moe_dispatch_combine": "tpuframe.ops.moe_gating",
    "moe_dispatch_combine_reference": "tpuframe.ops.moe_gating",
    "normalize_images": "tpuframe.ops.normalize",
    "normalize_images_reference": "tpuframe.ops.normalize",
    "fused_cross_entropy": "tpuframe.ops.cross_entropy",
    "cross_entropy_reference": "tpuframe.ops.cross_entropy",
    "FusedLayerNorm": "tpuframe.ops.layer_norm",
    "fused_layer_norm": "tpuframe.ops.layer_norm",
    "layer_norm_reference": "tpuframe.ops.layer_norm",
    "blockwise_attention": "tpuframe.ops.blockwise_attention",
    "blockwise_attention_reference": "tpuframe.ops.blockwise_attention",
    "ulysses_attention": "tpuframe.ops.ulysses",
    "ulysses_attention_local": "tpuframe.ops.ulysses",
    "attention_reference": "tpuframe.ops.ring_attention",
    "BlockDiffusionMask": "tpuframe.ops.ring_attention",
    "SlidingWindowMask": "tpuframe.ops.ring_attention",
    "SelectedKeysMask": "tpuframe.ops.ring_attention",
    "select_keys": "tpuframe.ops.sparse_index",
    "select_keys_reference": "tpuframe.ops.sparse_index",
    "ring_attention": "tpuframe.ops.ring_attention",
    "ring_attention_local": "tpuframe.ops.ring_attention",
    "unsort": "tpuframe.ops.unsort",
    "unsort_reference": "tpuframe.ops.unsort",
    "short_conv": "tpuframe.ops.short_conv",
    "short_conv_reference": "tpuframe.ops.short_conv",
    "conv_silu": "tpuframe.ops.short_conv",
    "conv_silu_reference": "tpuframe.ops.short_conv",
    "head_norm_rope": "tpuframe.ops.head_norm_rope",
    "head_norm_rope_reference": "tpuframe.ops.head_norm_rope",
    "gated_delta": "tpuframe.ops.gated_delta",
    "gated_delta_chunked": "tpuframe.ops.gated_delta",
    "gated_delta_reference": "tpuframe.ops.gated_delta",
    "kda": "tpuframe.ops.kda",
    "kda_chunked": "tpuframe.ops.kda",
    "kda_reference": "tpuframe.ops.kda",
    "bucket_abs_max": "tpuframe.ops.quant_wire",
    "bucket_abs_max_reference": "tpuframe.ops.quant_wire",
    "quant_encode": "tpuframe.ops.quant_wire",
    "quant_encode_reference": "tpuframe.ops.quant_wire",
    "quant_decode": "tpuframe.ops.quant_wire",
    "quant_decode_reference": "tpuframe.ops.quant_wire",
}

__all__ = sorted(_LAZY)


def _resolve(name):
    import importlib

    return getattr(importlib.import_module(_LAZY[name]), name)


def __getattr__(name):
    if name in _LAZY:
        return _resolve(name)
    raise AttributeError(f"module 'tpuframe.ops' has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + list(_LAZY)))


class _OpsModule(_types.ModuleType):
    """Eight exports share their kernel module's name
    (``blockwise_attention``, ``gated_delta``, ``grouped_matmul``,
    ``head_norm_rope``, ``kda``, ``ring_attention``, ``short_conv``,
    ``unsort``), and
    importing such a submodule makes the import machinery rebind the
    module object over the package attribute of the same name — which
    would shadow the function for every later
    ``from tpuframe.ops import ...``, import-order dependent.  Data
    descriptors on the module's class outrank instance attributes, so
    these properties keep resolving to the kernel *function* regardless
    of import order; the machinery's rebind is swallowed (the submodule
    itself stays importable through ``sys.modules``)."""


def _shadow_proof(name):
    return property(
        lambda _self: _resolve(name),
        lambda _self, _value: None,
    )


for _name in ("blockwise_attention", "gated_delta", "grouped_matmul", "head_norm_rope", "kda",
              "ring_attention", "short_conv", "unsort"):
    setattr(_OpsModule, _name, _shadow_proof(_name))

_sys.modules[__name__].__class__ = _OpsModule
