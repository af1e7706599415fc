"""Plain reference for ``keye-vl-2.0-30b-a3b``: one chip's share of the
language model and its next-token objective, float32 ``jax.numpy``, nothing of
the program imported.

Written from the source's ``config.json`` (``model_type`` ``KeyeVL2``: a
Qwen3-MoE-shaped decoder whose attention sees the keys a learned index
chooses, ``sa_config``) and the equations of ISSUE 47:

* a layer, pre-norm, no bias anywhere: ``h = x + Attn(RMSNorm(x))``, ``y = h +
  MoE(RMSNorm(h))``; every layer is sparse; a final RMSNorm and an untied head;
  mean next-token cross entropy over the vocabulary slice;
* Attn, with ``u`` the layer's normed input: ``q = W_q u`` (32 heads of 128),
  ``k = W_k u``, ``v = W_v u`` (4 heads of 128); every query head and every key
  head RMS-normalised over its 128 dimensions with a learned scale; the rotary
  turn (rotate-half, all 128 dimensions, theta 1e7, position ``t``: text rows
  give all three axes of ``mrope_section`` one position id, which is the plain
  turn).  The index: ``qI_j = (W_qI u)_j`` in R^64 for 16 index heads, one index
  key head ``kI = LayerNorm(W_kI u)`` in R^64 (scale and bias), ``w = W_wI u``
  in R^16, no rotary turn; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``;
  ``S_t`` the ``topk`` keys ``s <= t`` with the largest ``I[t, s]`` (every ``s <=
  t`` where ``t + 1 <= topk``; exactly ``topk`` otherwise, ties to the earlier
  key: `jax.lax.top_k`'s order), one set for all 32 heads, as a dense (L, L)
  mask a layer built from ``lax.top_k`` over a block of queries' scores.  Query
  head ``j`` uses key/value head ``j // 8``; scores ``q.k / sqrt(128)``; softmax
  over ``S_t`` alone; ``W_o`` of the concatenated heads.  The choice is
  discrete: the index is computed from ``stop_gradient(u)`` and no gradient of
  the objective reaches its four leaves;
* MoE: ``p = softmax(W_r u)`` over all 128 router outputs, the 8 largest, their
  gates divided by their sum (``norm_topk_prob``); ``y = sum over the chosen
  experts e held here of g_e W_down_e (silu(W_gate_e u) * W_up_e u)``.  This
  chip holds ``num_experts`` of the router's ``num_experts_published`` (experts
  ``held_first ..``); what the others would add is left out, as in the program.
  Every held expert is computed on every position and masked by membership in
  its top 8: no sort, no grouped product.  No shared expert.  Balance loss, the
  Switch form over all 128 outputs and all positions of the batch: ``coef *
  128 * sum_e (share of positions whose first choice is e) * (mean gate of e)``.

Departures, each under ``assumed`` in the configuration's file: the head norms
(the config has no key for them), the index's parts ``sa_config`` does not key
(the key norm's form, no bias, no rotary turn, chunk sizes that do not enter
the mathematics), ``router_aux_loss_coef``, text rows only, the seeded weights.

The Trainer reports the data loss and differentiates data loss + balance
loss.  ``loss`` returns ``data + (aux - stop_gradient(aux))``: its value is the
data loss, its gradient that of the whole objective.

``wrap`` decorates every matmul the configuration runs in bfloat16, the two of
attention, the index's two bfloat16 projections and its products, and each
expert's among them (the control rounds their operands); the router and the
index's head weights are float32 in the program too and are not wrapped.
Memory: a row's float32 scores are 256 MiB a head, so the heads are taken one at
a time under ``jax.checkpoint``, a key/value group at a time (``lax.map``), the
index a block of 512 queries at a time, and the experts one at a time as a
``lax.scan`` over the stacked weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
#: queries whose index scores are ranked at a time: (N, 16, 512, L) float32
_INDEX_BLOCK = 512


def _sizes(cfg) -> dict:
    sa = cfg["sa_config"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "expert": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
        "first": cfg["held_first"], "router": cfg["num_experts_published"],
        "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "ih": sa["indexer_num_heads"],
        "id": sa["indexer_head_dim"], "topk": sa["topk"],
    }


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    if cfg["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError("the index's equations are written for one index key head")
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    d, hd = z["d"], z["hd"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {
            "ln1": norm(d),
            "attn": {"query": {"kernel": mat(d, z["heads"] * hd)},
                     "key": {"kernel": mat(d, z["kv"] * hd)},
                     "value": {"kernel": mat(d, z["kv"] * hd)},
                     "q_norm": norm(hd), "k_norm": norm(hd),
                     "index_q": {"kernel": mat(d, z["ih"] * z["id"])},
                     "index_k": {"kernel": mat(d, z["id"])},
                     "index_k_norm": {"scale": ((z["id"],), "ones"),
                                      "bias": ((z["id"],), "zeros")},
                     "index_w": {"kernel": mat(d, z["ih"])},
                     "attn_out": {"kernel": ((z["heads"] * hd, d),
                                             ("normal", cfg["attn_out_init_std"]))}},
            "ln2": norm(d),
            "moe": {"router": {"kernel": mat(d, z["router"])},
                    "w_gate": mat(z["held"], d, z["expert"]),
                    "w_in": mat(z["held"], d, z["expert"]),
                    "w_out": mat(z["held"], z["expert"], d)},
        }
    tree["ln_f"] = norm(d)
    tree["lm_head"] = {"kernel": mat(d, z["vocab"])}
    return tree


def tables(length: int, dim: int, theta: float):
    """(cos, sin), each (length, dim) float32, rotate-half convention."""
    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(length, dtype=np.float64)[:, None] * f
    ang = np.concatenate([ang, ang], -1)
    return jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(np.sin(ang), jnp.float32)


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _turn(x, cos, sin):
    """Rotate-half rotary over the whole width of (N, T, H, width)."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def index_scores(qi, ki, w, wrap=_plain):
    """``I`` (N, Tq, Tk) for index queries (N, Tq, Hi, Di), the index key head
    (N, Tk, Di) and head weights (N, Tq, Hi): no mask."""
    s = wrap(lambda a, b: jnp.einsum("bqhd,bkd->bhqk", a, b, precision=_HI))(qi, ki)
    return jnp.einsum("bhqk,bqh->bqk", jnp.maximum(s, 0.0), w, precision=_HI)


def chosen_keys(p, x, cfg, wrap=_plain):
    """(N, T, T) bool, [row, query, key]: the keys each query sees."""
    z = _sizes(cfg)
    n, t, _ = x.shape
    x = lax.stop_gradient(x)
    qi = _mm(x, p["index_q"]["kernel"], wrap).reshape(n, t, z["ih"], z["id"])
    ki = _layer_norm(_mm(x, p["index_k"]["kernel"], wrap), p["index_k_norm"], z["eps"])
    # float32 in the program too, like the router: HIGHEST, and not wrapped
    w = jnp.dot(x, p["index_w"]["kernel"], precision=_HI)
    keys = jnp.arange(t)[None, None, :]
    if t <= z["topk"]:
        return jnp.broadcast_to(keys <= jnp.arange(t)[None, :, None], (n, t, t))
    block = min(_INDEX_BLOCK, t)
    if t % block:
        raise ValueError(f"rows of {t} positions are no whole blocks of {block} queries")

    def rows(args):
        q_blk, w_blk, first = args
        seen = keys <= (first + jnp.arange(block))[None, :, None]
        scores = jnp.where(seen, index_scores(q_blk, ki, w_blk, wrap), -jnp.inf)
        _, at = lax.top_k(scores, z["topk"])
        hit = jnp.zeros(scores.shape, bool).at[
            jnp.arange(n)[:, None, None], jnp.arange(block)[None, :, None], at].set(True)
        return hit & seen

    blocks = lambda a: jnp.moveaxis(a.reshape(n, t // block, block, *a.shape[2:]), 1, 0)  # noqa: E731
    chosen = lax.map(rows, (blocks(qi), blocks(w), jnp.arange(t // block) * block))
    return jnp.moveaxis(chosen, 0, 1).reshape(n, t, t)


def _head(q, k, v, seen, wrap):
    """One head over the chosen keys: (N, T, hd) each, ``seen`` (N, T, T)."""
    s = wrap(lambda a, b: jnp.einsum("bqd,bkd->bqk", a, b, precision=_HI))(q, k)
    p = jax.nn.softmax(jnp.where(seen, s * q.shape[-1] ** -0.5, -jnp.inf), axis=-1)
    return wrap(lambda a, b: jnp.einsum("bqk,bkd->bqd", a, b, precision=_HI))(p, v)


def _attn(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    n, t, _ = x.shape
    h, kv, hd = z["heads"], z["kv"], z["hd"]
    q = _mm(x, p["query"]["kernel"], wrap).reshape(n, t, h, hd)
    k = _mm(x, p["key"]["kernel"], wrap).reshape(n, t, kv, hd)
    v = _mm(x, p["value"]["kernel"], wrap).reshape(n, t, kv, hd)
    cos, sin = tables(t, hd, cfg["rope_theta"])
    q = _turn(_rms(q, p["q_norm"], z["eps"]), cos, sin)
    k = _turn(_rms(k, p["k_norm"], z["eps"]), cos, sin)
    seen = chosen_keys(p, x, cfg, wrap)
    head = jax.checkpoint(_head, static_argnums=(4,)) if remat else _head

    def group(qkv):
        """The h / kv query heads that share one key/value head."""
        qg, kg, vg = qkv                         # (N, T, h/kv, hd), (N, T, hd) x 2
        return jnp.stack([head(qg[:, :, j], kg, vg, seen, wrap)
                          for j in range(h // kv)], axis=2)

    if remat:
        group = jax.checkpoint(group)
    heads_first = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    out = lax.map(group, (heads_first(q.reshape(n, t, kv, h // kv, hd)),
                          heads_first(k), heads_first(v)))   # (kv, N, T, h/kv, hd)
    out = jnp.moveaxis(out, 0, 2).reshape(n, t, h * hd)
    return _mm(out, p["attn_out"]["kernel"], wrap)


def _gated(x, gate, up, down, wrap):
    return _mm(jax.nn.silu(_mm(x, gate, wrap)) * _mm(x, up, wrap), down, wrap)


def _moe(p, x, cfg, wrap, remat):
    """-> (this chip's part of the expert layer's output, the balance loss)."""
    z = _sizes(cfg)
    e, k = z["router"], z["k"]
    # the router is float32 in the program too: HIGHEST, and not wrapped
    probs = jax.nn.softmax(jnp.dot(x, p["router"]["kernel"], precision=_HI), -1)
    top_p, top_i = lax.top_k(probs, k)                       # (N, T, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    expert = jax.checkpoint(_gated, static_argnums=(4,)) if remat else _gated

    def add_expert(y, held):
        j, w_gate, w_in, w_out = held
        gate = jnp.sum(jnp.where(top_i == z["first"] + j, top_p, 0.0), -1)    # (N, T)
        return y + gate[..., None] * expert(x, w_gate, w_in, w_out, wrap), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(z["held"]), p["w_gate"], p["w_in"], p["w_out"]))
    first = jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32)
    aux = cfg["router_aux_loss_coef"] * e * jnp.sum(
        jnp.mean(first, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return y, aux


def _block(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    x = x + _attn(p["attn"], _rms(x, p["ln1"], z["eps"]), cfg, wrap, remat)
    y, aux = _moe(p["moe"], _rms(x, p["ln2"], z["eps"]), cfg, wrap, remat)
    return x + y, aux


class _Static:
    """The configuration as a static argument of ``jax.checkpoint``."""

    def __init__(self, cfg):
        self.cfg = cfg

    def __hash__(self):
        return id(self.cfg)

    def __eq__(self, other):
        return self.cfg is other.cfg


def _block_static(p, x, static, wrap, remat):
    return _block(p, x, static.cfg, wrap, remat)


def logits(params, inputs, cfg, wrap=_plain, remat=True):
    """(N, L) tokens -> ((N, L, vocab) float32 logits, the balance loss)."""
    x = params["embed"]["embedding"][inputs]
    block = (jax.checkpoint(_block_static, static_argnums=(2, 3, 4))
             if remat else _block_static)
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, a = block(params[f"block{i}"], x, _Static(cfg), wrap, remat)
        aux = aux + a
    x = _rms(x, params["ln_f"], cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], wrap), aux


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over the vocabulary slice; the gradient
    is that of it + the balance loss (see the module's docstring)."""
    lg, aux = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    data = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    return data + (aux - lax.stop_gradient(aux))
