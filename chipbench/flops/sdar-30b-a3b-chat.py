"""Operations ``sdar-30b-a3b-chat`` requires, counted from its shapes.

A sample is one block-diffusion training row: ``2 L`` positions (the noised
and the clean copy of ``L`` tokens) through every layer, ``L`` (the noised
half) through the head.  Per position, forward: one multiply-accumulate per
matmul parameter it passes through (the four attention projections, the
router; the embedding look-up is a gather).  The routed experts are counted
at their EXPECTED share of a pass: a position chooses ``num_experts_per_tok``
of the router's ``num_experts_published`` experts and this chip holds
``num_experts`` of them, so on average ``k * held / router`` (8 x 16 / 128 =
1) expert pass a position is required here, whatever the router does in one
step.  A training step requires 3 x forward at 2 FLOP per MAC; recomputation
never counts.

**Attention is counted at the mask's own area**, ``L^2 + L B`` scores a head
(the noised copy's own blocks ``L B``, its view of earlier clean blocks ``L
(L - B) / 2``, the clean copy's block-causal triangle ``L (L + B) / 2``), and
not at the full ``(2 L)^2`` square.  This departs from the full-square
convention of ``gpt2-medium`` and ``deepseek-v2-lite`` (PaLM's, for a causal
mask a factor of two that every model shares): here the square would credit
the chip with three quarters of a product that the objective never asks for,
and a kernel that visited all of it would read as the better one.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The four projections: q and o over all heads, k and v over the groups."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, routed_experts: int) -> int:
    """One block: attention with its two head norms, two RMSNorms, the router
    and the experts held."""
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


def mask_area(cfg: dict) -> int:
    """Scores a head that count in one row: ``L^2 + L B``."""
    return cfg["seq_len"] * (cfg["seq_len"] + cfg["block_length"])


def forward_macs_per_sample(cfg: dict) -> float:
    d, t = cfg["hidden_size"], cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    per_position = (attention_params(cfg) + d * cfg["num_experts_published"]
                    + expected_passes * expert_params(cfg))
    attention = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * mask_area(cfg)  # QK^T and PV
    return (cfg["num_hidden_layers"] * (2 * t * per_position + attention)
            + t * d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict) -> float:
    return 3 * 2 * forward_macs_per_sample(cfg)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each flash kernel as this
    configuration calls them (one call a layer each way), over the mask's
    area: the forward's two products a score; the backward's five (the scores
    again, since no flash backward can keep them, then dV, dP, dK, dQ).  Bytes:
    q, k, v and the output once (the backward: those, dO, and the three
    gradients once), bfloat16, k and v at their 4 heads.  The MXU bounds both."""
    rows = per_chip_batch * 2 * cfg["seq_len"] * cfg["head_dim"] * 2   # bytes a head
    q, kv = rows * cfg["num_attention_heads"], rows * cfg["num_key_value_heads"]
    product = (2 * per_chip_batch * cfg["num_attention_heads"] * cfg["head_dim"]
               * mask_area(cfg))
    return {
        "tpuframe_flash_fwd": {"bytes": 2 * q + 2 * kv, "flops": 2 * product},
        "tpuframe_flash_bwd": {"bytes": 4 * q + 4 * kv, "flops": 5 * product},
    }
