"""Operations ``keye-vl-2.0-30b-a3b`` requires, counted from its shapes.

A sample is one row of ``seq_len`` tokens through every layer and the head.
Per token, forward: one multiply-accumulate per matmul parameter it passes
through (the four attention projections, the router; the embedding look-up is
a gather).  The routed experts are counted at their EXPECTED share of a pass:
a token chooses ``num_experts_per_tok`` of the router's
``num_experts_published`` experts and this chip holds ``num_experts`` of them,
so on average ``k * held / router`` (8 x 8 / 128 = 0.5) expert pass a token is
required here, whatever the router does in one step.  A training step
requires 3 x forward at 2 FLOP per MAC; recomputation never counts.

**Attention is counted at the chosen pairs**: a query sees ``min(t + 1,
topk)`` keys, so a head's scores that count in a row are ``sum over t of
min(t + 1, topk)`` (14,681,088 at 8192 positions and 2048 keys a query,
43.75% of the causal triangle's 33,558,528), 128 wide, two products a score,
3 x forward.  This is the first configuration whose attention is priced at
less than the tiles any dense sweep visits: a sweep over the causal tiles
with the choice as a mask computes 2.29 times this, and is credited with
this.  **The index is forward-only work**: no gradient of the objective
reaches it (the choice is discrete), so its three projections and its scores
over the causal triangle (16 heads x 64 a pair) are counted ONCE, at 2 FLOP
per MAC and no backward pass.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The four projections: q and o over all heads, k and v over the groups."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def index_params(cfg: dict) -> int:
    """The index's three projections (its key norm's scale and bias apart)."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                                 + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, routed_experts: int) -> int:
    """One block: attention with its two head norms, the index with its key
    norm's scale and bias, two RMSNorms, the router and the experts held."""
    d = cfg["hidden_size"]
    return (attention_params(cfg) + 2 * cfg["head_dim"]
            + index_params(cfg) + 2 * cfg["sa_config"]["indexer_head_dim"] + 2 * d
            + d * cfg["num_experts_published"] + routed_experts * expert_params(cfg))


def total_params(cfg: dict, *, published: bool = False) -> int:
    """All parameters as the configuration is run here, or (``published``) of
    the whole language model the source describes."""
    layers = cfg["num_hidden_layers_published" if published else "num_hidden_layers"]
    experts = cfg["num_experts_published" if published else "num_experts"]
    vocab = cfg["vocab_size_published" if published else "vocab_size"]
    d = cfg["hidden_size"]
    return 2 * vocab * d + d + layers * layer_params(cfg, experts)


def causal_pairs(cfg: dict) -> int:
    """(query, key) pairs of one row with the key not after the query."""
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def chosen_pairs(cfg: dict) -> int:
    """(query, key) pairs of one row that attention computes: ``sum over t of
    min(t + 1, topk)``."""
    t = cfg["seq_len"]
    k = min(cfg["sa_config"]["topk"], t)
    return k * (k + 1) // 2 + (t - k) * k


def index_runs(cfg: dict) -> bool:
    """A row no longer than ``topk`` is plain causal attention: no index."""
    return cfg["seq_len"] > cfg["sa_config"]["topk"]


def index_macs_per_sample(cfg: dict) -> float:
    """One layer's index, forward: the projections a token and 16 x 64 a
    causal pair."""
    if not index_runs(cfg):
        return 0.0
    sa = cfg["sa_config"]
    return (cfg["seq_len"] * index_params(cfg)
            + sa["indexer_num_heads"] * sa["indexer_head_dim"] * causal_pairs(cfg))


def forward_macs_per_sample(cfg: dict) -> float:
    """What the backward pass doubles: everything but the index."""
    d, t = cfg["hidden_size"], cfg["seq_len"]
    expected_passes = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                       / cfg["num_experts_published"])
    per_token = (attention_params(cfg) + d * cfg["num_experts_published"]
                 + expected_passes * expert_params(cfg))
    products = 2 * cfg["num_attention_heads"] * cfg["head_dim"]   # QK^T and PV, a score
    return (cfg["num_hidden_layers"] * (t * per_token + products * chosen_pairs(cfg))
            + t * d * cfg["vocab_size"])


def train_flops_per_sample(cfg: dict) -> float:
    return (3 * 2 * forward_macs_per_sample(cfg)
            + 2 * cfg["num_hidden_layers"] * index_macs_per_sample(cfg))


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each kernel this
    configuration adds, whatever implements it.

    The flash kernels under the rule's suffix, over the chosen pairs: the
    forward's two products a score; the backward's five (the scores again,
    since no flash backward can keep them, then dV, dP, dK, dQ).  Bytes: q, k,
    v and the output once (the backward: those, dO, and the three gradients
    once), bfloat16, k and v at their 4 heads; what says which pairs count is
    not priced (an implementation may make it again inside a tile).  The MXU
    bounds both.

    The index kernel: 16 x 64 multiply-accumulates a causal pair, and the
    index queries, key head and head weights read once with one bit a causal
    pair written (the least that says a choice).  Finding the ``topk``-th
    largest of a row is compares and counts, which no published peak prices:
    its share of this roofline says how far from free the choice is."""
    rows = per_chip_batch * cfg["seq_len"] * cfg["head_dim"] * 2   # bytes a head
    q, kv = rows * cfg["num_attention_heads"], rows * cfg["num_key_value_heads"]
    product = (2 * per_chip_batch * cfg["num_attention_heads"] * cfg["head_dim"]
               * chosen_pairs(cfg))
    sa = cfg["sa_config"]
    tokens = per_chip_batch * cfg["seq_len"]
    index_bytes = (tokens * (2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
                             + 2 * sa["indexer_head_dim"] + 4 * sa["indexer_num_heads"])
                   + per_chip_batch * causal_pairs(cfg) // 8)
    return {
        "tpuframe_flash_fwd_select": {"bytes": 2 * q + 2 * kv, "flops": 2 * product},
        "tpuframe_flash_bwd_select": {"bytes": 4 * q + 4 * kv, "flops": 5 * product},
        "tpuframe_index_topk": {
            "bytes": index_bytes,
            "flops": (2 * per_chip_batch * sa["indexer_num_heads"] * sa["indexer_head_dim"]
                      * causal_pairs(cfg))},
    }
