"""Operations ``gpt2-medium`` requires, counted from its shapes.

Per token, forward: one multiply-accumulate per matmul parameter (the four
attention projections, the two MLP matrices, the untied output head; the
embedding look-ups are gathers) plus the two attention matmuls against
the whole sequence (the full T x T product, PaLM's MFU convention: the
program computes it whole under the causal mask).  A training step requires
3 x forward at 2 FLOP per MAC; recomputation never counts.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply every token: 12 d^2 per layer and d x vocab."""
    d = cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * 4 * d) + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """All parameters as the configuration is run (head untied, no attention bias)."""
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    per_layer = 4 * d * d + 8 * d * d + 4 * d + d + 4 * d   # matrices, MLP biases, two LayerNorms
    return v * d + t * d + cfg["n_layer"] * per_layer + 2 * d + d * v


def forward_macs_per_token(cfg: dict, seq_len: int) -> int:
    attention = cfg["n_layer"] * 2 * seq_len * cfg["n_embd"]   # QK^T and PV
    return matmul_params(cfg) + attention


def train_flops_per_sample(cfg: dict) -> float:
    t = cfg["seq_len"]
    return 3 * 2 * forward_macs_per_token(cfg, t) * t


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each Pallas kernel in the step."""
    elems = per_chip_batch * cfg["seq_len"] * cfg["n_embd"]
    return {
        # bfloat16 activations: forward reads x and writes y; backward reads x
        # and dy and writes dx; about 8 operations an element each way
        "tpuframe_layer_norm_fwd": {"bytes": 2 * elems * 2, "flops": 8 * elems},
        "tpuframe_layer_norm_bwd": {"bytes": 3 * elems * 2, "flops": 16 * elems},
    }
