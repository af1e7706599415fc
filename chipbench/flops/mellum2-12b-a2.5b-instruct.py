"""Operations ``mellum2-12b-a2.5b-instruct`` requires, counted from its shapes.

A sample is one row of ``seq_len`` tokens through every layer and the head.
Per token, forward: one multiply-accumulate per matmul parameter it passes
through (the four attention projections, the router; the embedding look-up is
a gather).  The routed experts are counted at their EXPECTED share of a pass:
a token chooses ``num_experts_per_tok`` of the router's
``num_experts_published`` experts and this chip holds ``num_experts`` of them,
so on average ``k * held / router`` (8 x 8 / 64 = 1) expert pass a token is
required here, whatever the router does in one step.  A training step
requires 3 x forward at 2 FLOP per MAC; recomputation never counts.

**Attention is counted at each kind of layer's own area**: the causal
triangle ``L (L + 1) / 2`` scores a head in a ``full_attention`` layer, the
band ``L W - W (W - 1) / 2`` (``W = sliding_window``; the triangle where ``L <=
W``) in a ``sliding_attention`` layer, as ``sdar-30b-a3b-chat`` counts its
mask's area and not the square: the square would credit the chip with
products the objective never asks for, three quarters of them in a window
layer at 8192 positions, and a kernel that visited all of it would read as the
better one.
"""

from __future__ import annotations


def layer_types(cfg: dict) -> list:
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def attention_params(cfg: dict) -> int:
    """The four projections: q and o over all heads, k and v over the groups."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, routed_experts: int) -> int:
    """One block of either kind: attention with its two head norms, two
    RMSNorms, the router and the experts held."""
    d = cfg["hidden_size"]
    return (attention_params(cfg) + 2 * cfg["head_dim"] + 2 * d
            + d * cfg["num_experts_published"] + routed_experts * expert_params(cfg))


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here, or (``published``) of
    the whole model the source describes."""
    layers = cfg["num_hidden_layers_published" if published else "num_hidden_layers"]
    experts = cfg["num_experts_published" if published else "num_experts"]
    vocab = cfg["vocab_size_published" if published else "vocab_size"]
    d = cfg["hidden_size"]
    return 2 * vocab * d + d + layers * layer_params(cfg, experts)


def mask_area(cfg: dict, kind: str) -> int:
    """Scores a head that count in one row under a layer of ``kind``."""
    t = cfg["seq_len"]
    w = min(cfg["sliding_window"], t) if kind == "sliding_attention" else t
    return t * w - w * (w - 1) // 2


def forward_macs_per_sample(cfg: dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    per_token = (attention_params(cfg) + d * cfg["num_experts_published"]
                 + expected_passes * expert_params(cfg))
    products = 2 * cfg["num_attention_heads"] * cfg["head_dim"]   # QK^T and PV, a score
    attention = sum(products * mask_area(cfg, kind) for kind in layer_types(cfg))
    return len(layer_types(cfg)) * t * per_token + attention + t * d * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict) -> float:
    return 3 * 2 * forward_macs_per_sample(cfg)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each flash kernel as this
    configuration calls them (one call a layer each way; a window layer's
    carry the rule's suffix), over the layer's own area: the forward's two
    products a score; the backward's five (the scores again, since no flash
    backward can keep them, then dV, dP, dK, dQ).  Bytes: q, k, v and the
    output once (the backward: those, dO, and the three gradients once),
    bfloat16, k and v at their 4 heads.  The MXU bounds all four."""
    rows = per_chip_batch * cfg["seq_len"] * cfg["head_dim"] * 2   # bytes a head
    q, kv = rows * cfg["num_attention_heads"], rows * cfg["num_key_value_heads"]
    costs = {}
    for kind, suffix in (("full_attention", ""), ("sliding_attention", "_window")):
        product = (2 * per_chip_batch * cfg["num_attention_heads"] * cfg["head_dim"]
                   * mask_area(cfg, kind))
        costs["tpuframe_flash_fwd" + suffix] = {"bytes": 2 * q + 2 * kv, "flops": 2 * product}
        costs["tpuframe_flash_bwd" + suffix] = {"bytes": 4 * q + 4 * kv, "flops": 5 * product}
    return costs
