"""Operations ``kimi-linear-48b-a3b-instruct`` requires, counted from its shapes.

A sample is one row of ``seq_len`` tokens through every layer and the head.
Per token, forward: one multiply-accumulate per matmul parameter it passes
through (a KDA layer's fused q/k/v projection, its two low-rank pairs, beta's
projection and the output projection; the latent layer's four; the dense MLP
or the router and the shared expert; the embedding look-up is a gather, the
convolution's 4 taps a channel are no matmul and are left out).  The routed
experts are counted at their EXPECTED share of a pass: a token chooses
``num_experts_per_token`` of the router's ``num_experts_published`` experts
and this chip holds ``num_experts`` of them, so on average ``k * held /
router`` (8 x 8 / 256 = 0.25) expert pass a token is required here, whatever
the router does in one step.  A training step requires 3 x forward at 2 FLOP
per MAC; recomputation never counts.

**The rule is counted at the recurrence's own operations**, not at what a
chunked schedule computes in its place: per position and head, over the (d_k,
d_v) state, the decay (one multiply an element: the vector decay's factor is
a row's, the count an element is the scalar rule's), ``S^T k``, the rank-one
write and ``S^T q`` (a multiply and an add an element each), and one more
multiply an element for the vector decay's ``exp(g)`` laid over the state's
rows: 8 d_k d_v FLOP forward; backward twice that.  A change of chunk length,
or of what the kernels fuse, leaves the count standing.

Latent attention is counted at the causal triangle ``L (L + 1) / 2`` scores a
head: 192 wide for ``q k^T``, 128 for ``p v``.
"""

from __future__ import annotations

#: positions between two states a backward pass has to keep (the source's own
#: chunk length): what ``kernel_costs`` prices as the least bytes
RULE_CHUNK = 64


def layer_types(cfg: dict) -> list:
    la = cfg["linear_attn_config"]
    return ["kda" if layer in la["kda_layers"] else "full_attention"
            for layer in cfg["layers_held"]]


def _kda_sizes(cfg: dict) -> tuple[int, int]:
    la = cfg["linear_attn_config"]
    return la["num_heads"], la["head_dim"]


def kda_matmul_params(cfg: dict) -> int:
    """q, k, v and o; the decay's pair and the output gate's; beta."""
    d, low = cfg["hidden_size"], cfg["kda_low_rank"]
    h, dk = _kda_sizes(cfg)
    return 4 * d * h * dk + 2 * (d * low + low * h * dk) + d * h


def kda_params(cfg: dict) -> int:
    """A KDA mixer: its matrices, the taps, ``A_log``, ``dt_bias`` and the
    gated norm's scale."""
    h, dk = _kda_sizes(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return kda_matmul_params(cfg) + taps * 3 * h * dk + h + h * dk + dk


def latent_matmul_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d


def latent_params(cfg: dict) -> int:
    return latent_matmul_params(cfg) + cfg["kv_lora_rank"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_shared_params(cfg: dict) -> int:
    """What every token passes through in an expert layer: the router and
    the shared expert."""
    d = cfg["hidden_size"]
    return (d * cfg["num_experts_published"]
            + 3 * d * cfg["num_shared_experts"] * cfg["moe_intermediate_size"])


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict, layer: int, kind: str, routed_experts: int) -> int:
    """Published layer ``layer`` (1-based): mixer, two norms, and the dense
    MLP or the expert layer (with its selection bias)."""
    mixer = kda_params(cfg) if kind == "kda" else latent_params(cfg)
    if layer <= cfg["first_k_dense_replace"]:
        ffn = dense_params(cfg)
    else:
        ffn = (moe_shared_params(cfg) + cfg["num_experts_published"]
               + routed_experts * expert_params(cfg))
    return mixer + 2 * cfg["hidden_size"] + ffn


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here, or (``published``) of
    the whole model the source describes."""
    experts = cfg["num_experts_published" if published else "num_experts"]
    vocab = cfg["vocab_size_published" if published else "vocab_size"]
    held = (list(range(1, cfg["num_hidden_layers_published"] + 1)) if published
            else cfg["layers_held"])
    d = cfg["hidden_size"]
    kinds = layer_types({**cfg, "layers_held": held})
    return 2 * vocab * d + d + sum(
        layer_params(cfg, layer, kind, experts) for layer, kind in zip(held, kinds))


def causal_area(cfg: dict) -> int:
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def rule_flops_forward(cfg: dict) -> int:
    """The recurrence's operations for one row through one layer, forward."""
    h, dk = _kda_sizes(cfg)
    return 8 * dk * dk * h * cfg["seq_len"]


def forward_macs_per_sample(cfg: dict) -> float:
    """Multiply-accumulates of the matmuls and of attention's two products;
    the rule's operations are `rule_flops_forward`'s."""
    t = cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_token"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    per_token = 0.0
    for layer, kind in zip(cfg["layers_held"], layer_types(cfg)):
        per_token += kda_matmul_params(cfg) if kind == "kda" else latent_matmul_params(cfg)
        per_token += (dense_params(cfg) if layer <= cfg["first_k_dense_replace"]
                      else moe_shared_params(cfg) + expected_passes * expert_params(cfg))
    products = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    attention = layer_types(cfg).count("full_attention") * products * causal_area(cfg)
    return t * per_token + attention + t * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict) -> float:
    rule = layer_types(cfg).count("kda") * rule_flops_forward(cfg)
    return 3 * (2 * forward_macs_per_sample(cfg) + rule)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each kernel this
    configuration adds (one call a KDA layer each way).

    The rule's kernels at the recurrence's operations and the least bytes any
    schedule moves: q, k and v in and o out in bfloat16, g (a number a key
    channel) and beta in float32, one float32 state a chunk boundary
    (`RULE_CHUNK`) written for the backward pass; backward reads those and dO
    and writes the five gradients.  HBM bounds both."""
    rows = per_chip_batch * cfg["seq_len"]
    h, dk = _kda_sizes(cfg)
    head = rows * h * dk * 2                                         # q, k, v or o: bytes
    g = rows * h * dk * 4
    beta = rows * h * 4
    states = per_chip_batch * (cfg["seq_len"] // RULE_CHUNK) * h * dk * dk * 4
    rule = per_chip_batch * rule_flops_forward(cfg)
    return {
        "tpuframe_kda_fwd": {"bytes": 4 * head + g + beta + states, "flops": rule},
        "tpuframe_kda_bwd": {"bytes": 2 * (3 * head + g + beta) + 2 * head + states,
                             "flops": 2 * rule},
    }
