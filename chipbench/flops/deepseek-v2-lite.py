"""Operations ``deepseek-v2-lite`` requires, counted from its shapes.

Per token, forward: one multiply-accumulate per matmul parameter the token
passes through (the latent attention's four projections, the dense layer's
gated MLP, the router, the shared MLP, the output head over the vocabulary
slice; the embedding look-up is a gather) plus the two attention matmuls
against the whole sequence (the full T x T product, PaLM's MFU convention, as
``gpt2-medium`` counts it: 192-wide scores, 128-wide values).  The routed
experts are counted at their EXPECTED share of a pass: a token chooses
``num_experts_per_tok`` of the router's ``n_routed_experts_published``
experts and this chip holds ``n_routed_experts`` of them, so on average
``k * held / router`` (6 x 8 / 64 = 0.75) expert passes a token are required
here, whatever the router does in one step.  A training step requires 3 x
forward at 2 FLOP per MAC; recomputation never counts.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"], cfg["kv_lora_rank"])
    return d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, sparse: bool, routed_experts: int) -> int:
    """One block: attention with its latent norm, two RMSNorms, and the FFN."""
    d = cfg["hidden_size"]
    p = attention_params(cfg) + cfg["kv_lora_rank"] + 2 * d
    if not sparse:
        return p + 3 * d * cfg["intermediate_size"]
    return (p + d * cfg["n_routed_experts_published"]
            + cfg["n_shared_experts"] * expert_params(cfg)
            + routed_experts * expert_params(cfg))


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here, or (``published``) of
    the whole model the source describes: 15,706,484,224."""
    layers = cfg["num_hidden_layers_published" if published else "num_hidden_layers"]
    experts = cfg["n_routed_experts_published" if published else "n_routed_experts"]
    vocab = cfg["vocab_size_published" if published else "vocab_size"]
    d, dense = cfg["hidden_size"], cfg["first_k_dense_replace"]
    return (2 * vocab * d + d
            + dense * layer_params(cfg, False, 0)
            + (layers - dense) * layer_params(cfg, True, experts))


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    scores = seq_len * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    expected_passes = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                       / cfg["n_routed_experts_published"])
    sparse = (d * cfg["n_routed_experts_published"]
              + cfg["n_shared_experts"] * expert_params(cfg)
              + expected_passes * expert_params(cfg))
    return (layers * (attention_params(cfg) + scores)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * sparse
            + d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict) -> float:
    t = cfg["seq_len"]
    return 3 * 2 * forward_macs_per_token(cfg, t) * t


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """No ``tpuframe_*`` Pallas kernel runs in this configuration's step: its
    norms are plain jnp, and the grouped expert product is XLA's own
    ragged-dot kernel, which the trace names after the HLO op."""
    return {}


def grouped_matmul_flops(cfg: dict, assignments: float) -> float:
    """FLOPs the three grouped products of one sparse layer require, forward
    and backward, for ``assignments`` (token, choice) pairs routed here."""
    return 3 * 2 * assignments * expert_params(cfg)
