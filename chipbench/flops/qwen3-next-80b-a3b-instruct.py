"""Operations ``qwen3-next-80b-a3b-instruct`` requires, counted from its shapes.

A sample is one row of ``seq_len`` tokens through every layer and the head.
Per token, forward: one multiply-accumulate per matmul parameter it passes
through (a linear-attention layer's two fused input projections and its
output projection; a full-attention layer's four projections, the query's
twice as wide for the gate; the router, the shared expert and its gate; the
embedding look-up is a gather, the convolution's 4 taps a channel are no
matmul and are left out).  The routed experts are counted at their EXPECTED
share of a pass: a token chooses ``num_experts_per_tok`` of the router's
``num_experts_published`` experts and this chip holds ``num_experts`` of them,
so on average ``k * held / router`` (10 x 16 / 512 = 0.3125) expert pass a
token is required here, whatever the router does in one step.  A training
step requires 3 x forward at 2 FLOP per MAC; recomputation never counts.

**The gated delta rule is counted at the recurrence's own operations**, not
at what a chunked schedule computes in its place: per position and value
head, over the (d_k, d_v) state, the decay (one multiply an element), ``S^T
k``, the rank-one write and ``S^T q`` (a multiply and an add an element
each): 7 d_k d_v FLOP forward; backward twice that (each product's two
transposes), as for any matmul.  A change of chunk length, or of what the
kernels fuse, leaves the count standing.

Full attention is counted at the causal triangle ``L (L + 1) / 2`` scores a
head, 256 wide.
"""

from __future__ import annotations

#: positions between two states a backward pass has to keep (the source's own
#: chunk length): what ``kernel_costs`` prices as the least bytes
RULE_CHUNK = 64


def layer_types(cfg: dict) -> list:
    every = cfg["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in cfg["layers_held"]]


def linear_matmul_params(cfg: dict) -> int:
    """The two fused input projections and the output projection."""
    d = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return d * (2 * keys + 2 * values) + d * 2 * cfg["linear_num_value_heads"] + values * d


def linear_params(cfg: dict) -> int:
    """A linear-attention mixer: its matrices, the taps, ``A_log``,
    ``dt_bias`` and the gated norm's scale."""
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return (linear_matmul_params(cfg) + cfg["linear_conv_kernel_dim"] * (2 * keys + values)
            + 2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"])


def attention_matmul_params(cfg: dict) -> int:
    """q (with its gate) and o over all heads, k and v over the groups."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * (3 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def attention_params(cfg: dict) -> int:
    return attention_matmul_params(cfg) + 2 * cfg["head_dim"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_shared_params(cfg: dict) -> int:
    """What every token passes through in an expert layer: the router, the
    shared expert and its gate."""
    d = cfg["hidden_size"]
    return (d * cfg["num_experts_published"] + 3 * d * cfg["shared_expert_intermediate_size"] + d)


def layer_params(cfg: dict, kind: str, routed_experts: int) -> int:
    mixer = linear_params(cfg) if kind == "linear_attention" else attention_params(cfg)
    return (mixer + 2 * cfg["hidden_size"] + moe_shared_params(cfg)
            + routed_experts * expert_params(cfg))


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here, or (``published``) of
    the whole model the source describes."""
    experts = cfg["num_experts_published" if published else "num_experts"]
    vocab = cfg["vocab_size_published" if published else "vocab_size"]
    held = (range(cfg["num_hidden_layers_published"]) if published else cfg["layers_held"])
    d = cfg["hidden_size"]
    return 2 * vocab * d + d + sum(
        layer_params(cfg, kind, experts) for kind in layer_types({**cfg, "layers_held": held}))


def causal_area(cfg: dict) -> int:
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def rule_flops_forward(cfg: dict) -> int:
    """The recurrence's operations for one row through one layer, forward."""
    state = cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return 7 * state * cfg["linear_num_value_heads"] * cfg["seq_len"]


def forward_macs_per_sample(cfg: dict) -> float:
    """Multiply-accumulates of the matmuls and of attention's two products;
    the rule's operations are `rule_flops_forward`'s."""
    t = cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    kinds = layer_types(cfg)
    per_token = sum(linear_matmul_params(cfg) if kind == "linear_attention"
                    else attention_matmul_params(cfg) for kind in kinds)
    per_token += len(kinds) * (moe_shared_params(cfg) + expected_passes * expert_params(cfg))
    products = 2 * cfg["num_attention_heads"] * cfg["head_dim"]   # QK^T and PV, a score
    attention = kinds.count("full_attention") * products * causal_area(cfg)
    return t * per_token + attention + t * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict) -> float:
    rule = layer_types(cfg).count("linear_attention") * rule_flops_forward(cfg)
    return 3 * (2 * forward_macs_per_sample(cfg) + rule)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each kernel as this
    configuration calls them (one call a layer each way).

    The flash kernels over the causal triangle at 256-wide heads: the
    forward's two products a score, the backward's five; q, k, v and the
    output once (the backward: those, dO, and the three gradients once),
    bfloat16, k and v at their 2 heads.  The MXU bounds both.

    The gated delta rule's kernels at the recurrence's operations and the
    least bytes any schedule moves: q and k (at their 16 heads), v in and o
    out in bfloat16, g and beta in float32, one float32 state a chunk
    boundary (`RULE_CHUNK`) written for the backward pass; backward reads
    those and dO and writes the five gradients.  HBM bounds both."""
    rows = per_chip_batch * cfg["seq_len"]
    head = rows * cfg["head_dim"] * 2                               # bytes a head
    q, kv = head * cfg["num_attention_heads"], head * cfg["num_key_value_heads"]
    product = (2 * per_chip_batch * cfg["num_attention_heads"] * cfg["head_dim"]
               * causal_area(cfg))
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qk = 2 * rows * hk * dk * 2
    v = rows * hv * dv * 2
    scalars = 2 * rows * hv * 4
    states = per_chip_batch * (cfg["seq_len"] // RULE_CHUNK) * hv * dk * dv * 4
    rule = per_chip_batch * rule_flops_forward(cfg)
    return {
        "tpuframe_flash_fwd": {"bytes": 2 * q + 2 * kv, "flops": 2 * product},
        "tpuframe_flash_bwd": {"bytes": 4 * q + 4 * kv, "flops": 5 * product},
        "tpuframe_gated_delta_fwd": {"bytes": qk + 2 * v + scalars + states, "flops": rule},
        "tpuframe_gated_delta_bwd": {"bytes": 2 * (qk + v + scalars) + 2 * v + states,
                                     "flops": 2 * rule},
    }
