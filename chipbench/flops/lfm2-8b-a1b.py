"""Operations ``lfm2-8b-a1b`` requires, counted from its shapes.

Per token, forward: one multiply-accumulate per matmul parameter the token
passes through (a conv layer's two projections, an attention layer's four,
the dense layer's gated MLP, the router, the output head over the vocabulary
slice; the embedding look-up is a gather), one per tap and channel of a conv
layer's depthwise convolution and one for each of its two gates.  The routed
experts are counted at their EXPECTED share of a pass: a token chooses
``num_experts_per_tok`` of the router's ``num_experts_published`` experts and
this chip holds ``num_experts`` of them, so on average ``k * held / router``
(4 x 8 / 32 = 1) expert pass a token is required here, whatever the router
does in one step.  A training step requires 3 x forward at 2 FLOP per MAC;
recomputation never counts.

**Attention is counted at the causal mask's own area**, ``T (T + 1) / 2``
scores a head, as ``sdar-30b-a3b-chat`` counts its mask's area and not as
``gpt2-medium`` and ``deepseek-v2-lite`` count the full square: the objective
asks for no score above the diagonal.
"""

from __future__ import annotations


def layer_types(cfg: dict, *, published: bool = False) -> list:
    if published:
        return list(cfg["layer_types"])
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_params(cfg: dict) -> int:
    """The four projections: q and o over all heads, k and v over the groups."""
    d = cfg["hidden_size"]
    return 2 * d * head_dim(cfg) * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def conv_params(cfg: dict) -> int:
    """The operator's two projections (the taps are counted beside them)."""
    return 4 * cfg["hidden_size"] ** 2


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, kind: str, sparse: bool, routed_experts: int) -> int:
    """One block: its mixer, two RMSNorms and its feed-forward part."""
    d = cfg["hidden_size"]
    mixer = (conv_params(cfg) + cfg["conv_L_cache"] * d if kind == "conv"
             else attention_params(cfg) + 2 * head_dim(cfg))
    ffn = (d * cfg["num_experts_published"] + cfg["num_experts_published"]
           + routed_experts * expert_params(cfg)) if sparse else 3 * d * cfg["intermediate_size"]
    return mixer + 2 * d + ffn


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here (541,374,720), or
    (``published``) of the whole model the source describes with an untied
    head: 8,474,148,288."""
    pick = lambda key: cfg[f"{key}_published" if published else key]  # noqa: E731
    d = cfg["hidden_size"]
    return 2 * pick("vocab_size") * d + d + sum(
        layer_params(cfg, kind, i >= pick("num_dense_layers"), pick("num_experts"))
        for i, kind in enumerate(layer_types(cfg, published=published)))


def causal_area(cfg: dict) -> int:
    """Scores a head that count in one row: ``T (T + 1) / 2``."""
    return cfg["seq_len"] * (cfg["seq_len"] + 1) // 2


def forward_macs_per_sample(cfg: dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    sparse = d * cfg["num_experts_published"] + expected_passes * expert_params(cfg)
    per_token = attention = 0
    for i, kind in enumerate(layer_types(cfg)):
        if kind == "conv":
            per_token += conv_params(cfg) + (cfg["conv_L_cache"] + 2) * d
        else:
            per_token += attention_params(cfg)
            attention += 2 * cfg["num_attention_heads"] * head_dim(cfg) * causal_area(cfg)
        per_token += sparse if i >= cfg["num_dense_layers"] else 3 * d * cfg["intermediate_size"]
    return t * (per_token + d * cfg["vocab_size"]) + attention


def train_flops_per_sample(cfg: dict) -> float:
    return 3 * 2 * forward_macs_per_sample(cfg)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each kernel as this
    configuration calls them (a call a layer each way).

    The short-convolution pair moves whole (tokens, D) bfloat16 arrays and
    bandwidth bounds both: the forward reads B, C, h and writes the result
    (4 arrays), the backward reads those and the result's gradient and writes
    the three gradients (7).  Operations: a multiply for each of the two gates
    and a multiply-add a tap forward; backward the same again for ``z`` and
    ``c``, and the taps' transpose, the taps' own gradient and the two gates'
    transposes.  The flash pair: the causal area's two products a score
    forward, five backward (the scores again, then dV, dP, dK, dQ); q, k, v
    and the output once (the backward: those, dO, and the three gradients),
    k and v at their 8 heads.  The MXU bounds both."""
    d, k = cfg["hidden_size"], cfg["conv_L_cache"]
    plane = per_chip_batch * cfg["seq_len"] * d * 2           # bytes of a (tokens, D) array
    elems = per_chip_batch * cfg["seq_len"] * d
    rows = per_chip_batch * cfg["seq_len"] * head_dim(cfg) * 2   # bytes a head
    q, kv = rows * cfg["num_attention_heads"], rows * cfg["num_key_value_heads"]
    product = 2 * per_chip_batch * cfg["num_attention_heads"] * head_dim(cfg) * causal_area(cfg)
    return {
        "tpuframe_short_conv_fwd": {"bytes": 4 * plane, "flops": (2 + 2 * k) * elems},
        "tpuframe_short_conv_bwd": {"bytes": 7 * plane, "flops": (6 + 6 * k) * elems},
        "tpuframe_flash_fwd": {"bytes": 2 * q + 2 * kv, "flops": 2 * product},
        "tpuframe_flash_bwd": {"bytes": 4 * q + 4 * kv, "flops": 5 * product},
    }
