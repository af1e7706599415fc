"""The registry of dispatchable ops, and the names they go by.

Three things that every layer above ``ops/`` reads, with no jax behind
them:

- :data:`OPS_REGISTRY`: every kernel module under ``ops/`` with its
  entry point, its jnp oracle and the parity test that pins kernel ==
  oracle.  The lint OP family cross-checks all three directions (module
  listed, symbol exists, test exists), so an op cannot ship
  unregistered or untested.
- the **name map**: profiler op names (HLO base names off a parsed
  capture's ``top_ops`` rows) normalize to dispatchable tpuframe ops,
  so a diagnosis detail names ``cross_entropy``, not
  ``log_softmax_fusion``.
- :func:`shape_class`: the power-of-two bucket names an
  ``ops/kernel_verdict`` event carries.

Which implementation of an op runs is ``ops/dispatch.py``'s
``resolve_interpret`` and the op's own shape rule; nothing here decides
it.  Stdlib-only (the doctor enumerates the ops on wedged-backend
processes).
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

__all__ = [
    "OPS_REGISTRY",
    "OP_NAME_TOKENS",
    "map_op_name",
    "normalize_top_ops",
    "shape_class",
]

#: the dispatch registry: every kernel module under ``ops/`` appears
#: here with its entry point, its jnp oracle, and the parity test that
#: pins kernel == oracle.  The lint OP family cross-checks all three
#: directions (module listed, symbol exists, test exists), so this dict
#: must stay a pure literal.
OPS_REGISTRY = {
    "normalize": {
        "module": "tpuframe.ops.normalize",
        "symbol": "normalize_images",
        "reference": "normalize_images_reference",
        "parity_test": "tests/test_ops.py::test_normalize_matches_reference",
    },
    "cross_entropy": {
        "module": "tpuframe.ops.cross_entropy",
        "symbol": "fused_cross_entropy",
        "reference": "cross_entropy_reference",
        "parity_test": "tests/test_ops.py::test_fused_cross_entropy_forward",
    },
    "layer_norm": {
        "module": "tpuframe.ops.layer_norm",
        "symbol": "fused_layer_norm",
        "reference": "layer_norm_reference",
        "parity_test":
            "tests/test_layer_norm.py::TestFusedLayerNorm::test_forward_matches_oracle",
    },
    "quant_wire": {
        "module": "tpuframe.ops.quant_wire",
        "symbol": "quant_encode",
        "reference": "quant_encode_reference",
        "parity_test":
            "tests/test_comms_fused.py::TestQuantWireKernels::test_amax_and_encode_bit_exact",
    },
    "blockwise_attention": {
        "module": "tpuframe.ops.blockwise_attention",
        "symbol": "blockwise_attention",
        "reference": "blockwise_attention_reference",
        "parity_test":
            "tests/test_blockwise_attention.py::test_kernel_matches_scan_schedule",
    },
    "ring_attention": {
        "module": "tpuframe.ops.ring_attention",
        "symbol": "ring_attention",
        "reference": "attention_reference",
        "parity_test": "tests/test_ring_attention.py::test_ring_matches_full",
    },
    "ulysses": {
        "module": "tpuframe.ops.ulysses",
        "symbol": "ulysses_attention",
        "reference": None,
        "parity_test": "tests/test_ulysses.py::test_ulysses_matches_full",
    },
    "grouped_matmul": {
        "module": "tpuframe.ops.grouped_matmul",
        "symbol": "grouped_matmul",
        "reference": "grouped_matmul_reference",
        "parity_test":
            "tests/test_latent_moe.py::TestGroupedMatmul::test_forward_and_both_gradients_with_empty_groups",
    },
    "unsort": {
        "module": "tpuframe.ops.unsort",
        "symbol": "unsort",
        "reference": "unsort_reference",
        "parity_test": "tests/test_unsort.py::TestKernel::test_the_cells_shapes_against_xlas_form",
    },
    "short_conv": {
        "module": "tpuframe.ops.short_conv",
        "symbol": "short_conv",
        "reference": "short_conv_reference",
        "parity_test": "tests/test_lfm2.py::TestShortConvOp::test_kernels_match_the_oracle",
    },
    "conv_silu": {
        "module": "tpuframe.ops.short_conv",
        "symbol": "conv_silu",
        "reference": "conv_silu_reference",
        "parity_test": "tests/test_conv_silu.py::TestConvSiluOp::test_kernels_match_the_oracle",
    },
    "head_norm_rope": {
        "module": "tpuframe.ops.head_norm_rope",
        "symbol": "head_norm_rope",
        "reference": "head_norm_rope_reference",
        "parity_test": "tests/test_head_norm_rope.py::TestKernels::test_kernels_match_the_oracle",
    },
    "gated_delta": {
        "module": "tpuframe.ops.gated_delta",
        "symbol": "gated_delta",
        "reference": "gated_delta_reference",
        "parity_test": "tests/test_gated_delta.py::TestAgainstTheRecurrence::test_outputs_and_all_five_gradients",
    },
    "kda": {
        "module": "tpuframe.ops.kda",
        "symbol": "kda",
        "reference": "kda_reference",
        "parity_test": "tests/test_kimi_linear.py::TestTheOpAgainstTheRecurrence::test_outputs_and_all_five_gradients",
    },
    "sparse_index": {
        "module": "tpuframe.ops.sparse_index",
        "symbol": "select_keys",
        "reference": "select_keys_reference",
        "parity_test": "tests/test_keye_vl2.py::TestIndexOp::test_the_kernel_matches_the_oracle",
    },
    "moe_gating": {
        "module": "tpuframe.ops.moe_gating",
        "symbol": "moe_dispatch_combine",
        "reference": "moe_dispatch_combine_reference",
        "parity_test":
            "tests/test_moe.py::TestMoEGatingKernel::test_fused_matches_reference",
    },
}

# -- profiler-name -> tpuframe-op map -----------------------------------------

#: ordered (op, name tokens) pairs: the first op whose token appears in
#: a profiler base name claims the row.  Tokens are matched on the
#: lowercased base name (``device_time._base_name`` output), which for
#: XLA fusions carries the root-op hint (``log_softmax_fusion``,
#: ``layer_norm.clone``); a generic name (``fusion``, ``dot``) maps to
#: no op and keeps its raw name.
OP_NAME_TOKENS = (
    ("cross_entropy", ("cross_entropy", "log_softmax", "softmax", "nll")),
    ("layer_norm", ("layer_norm", "layernorm", "rms_norm")),
    ("normalize", ("normalize", "per_image_standard")),
    ("quant_wire", ("quant", "dequant", "stochastic_round")),
    ("attention", ("attention", "flash", "fmha", "scaled_dot_product")),
    ("short_conv", ("short_conv",)),
    ("conv_silu", ("conv_silu",)),
    ("head_norm_rope", ("head_norm_rope",)),
    ("gated_delta", ("gated_delta",)),
    ("kda", ("tpuframe_kda",)),
    ("sparse_index", ("tpuframe_index",)),
    ("unsort", ("tpuframe_unsort",)),
    ("grouped_matmul", ("tpuframe_grouped", "ragged-dot", "ragged_dot", "grouped_matmul")),
    ("moe_gating", ("top_k_gating", "moe", "expert_dispatch")),
)


def map_op_name(name: str) -> str | None:
    """The tpuframe op a profiler op name belongs to, or None."""
    low = (name or "").lower()
    for op, tokens in OP_NAME_TOKENS:
        if any(tok in low for tok in tokens):
            return op
    return None


def normalize_top_ops(top_ops: list[dict]) -> list[dict]:
    """``device_time.top_ops`` rows with the profiler name normalized:
    each row gains ``op`` (the dispatchable tpuframe op, or None) and
    ``raw`` (the profiler name), and ``name`` becomes the actionable
    one — what a diagnosis detail or a dashboard should print."""
    out = []
    for row in top_ops or []:
        raw = row.get("name") or ""
        op = map_op_name(raw)
        r = dict(row)
        r["raw"] = raw
        r["op"] = op
        r["name"] = op or raw
        out.append(r)
    return out


# -- shape classes ------------------------------------------------------------

def shape_class(**dims: int) -> str | None:
    """A stable bucket for a shape: each named dim rounds UP to the next
    power of two (``shape_class(b=200, k=1000) == 'b256_k1024'``), so
    nearby shapes share one verdict event.

    Returns None when a dim is not a concrete integer — under
    ``jax.export`` shape polymorphism the batch dims are symbolic and
    refuse ``int()`` — and the event carries no class instead of the
    export trace aborting."""
    parts = []
    for k in sorted(dims):
        try:
            v = max(1, int(dims[k]))
        except Exception:
            return None
        p = 1
        while p < v:
            p <<= 1
        parts.append(f"{k}{p}")
    return "_".join(parts)
