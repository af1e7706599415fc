"""Plain reference for ``deepseek-v2-lite``: one chip's share of the model,
float32 ``jax.numpy``, nothing of the program imported.

Written from the source's config and the DeepSeek-V2 paper's equations
(arXiv:2405.04434, sections 2.1 and 2.2; YaRN: arXiv:2309.00071):

* block, pre-norm: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
  the first ``first_k_dense_replace`` layers take a dense SiLU-gated MLP, the
  others the sparse FFN; a final RMSNorm, an untied head, mean next-token
  cross entropy;
* MLA with no query latent: ``q = x W_q`` -> per head ``[q_nope | q_rope]``;
  ``x W_kva -> [c | k_rope]``, ``RMSNorm(c) W_kvb`` -> per head
  ``[k_nope | v]``; rotary (YaRN frequencies, rotate-half) on ``q_rope`` of
  each head and on the one ``k_rope`` all heads share; scores
  ``q.k * width^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
* sparse FFN: ``p = softmax(x W_g)`` over all the router's experts, greedy
  top k, gate = ``p`` as it is; ``y = sum_{e in top k, e held here} p_e E_e(x)
  + S(x)``: this chip holds ``n_routed_experts`` of the router's
  ``n_routed_experts_published`` (experts ``held_first ..``), and what the
  others would add is left out, as in the program.  Every held expert is
  computed on every token and masked by membership in the token's top k: no
  sort, no grouped product;
* balance loss per sparse layer, over all the router's outputs, per sequence
  (``seq_aux``): ``alpha * mean_b sum_e f_be P_be``, ``f_be = count_t(e in top
  k) E / (k T)``, ``P_be = mean_t p``.

Departure, under ``assumed`` in the configuration's file: the source
de-interleaves the rotary columns before rotating; with weights from a seed
that is a column permutation of ``W_q`` and ``W_kva`` that nothing can tell
apart, and it is left out.

The Trainer reports the data loss and differentiates data loss + balance
loss.  ``loss`` returns ``ce + (aux - stop_gradient(aux))``: its value is the
data loss, its gradient that of the whole objective, so the harness's one
scalar gives both.

``wrap`` decorates every matmul, each expert's among them (the control
rounds their operands).  Memory: one attention head at a time and one expert
at a time under ``jax.checkpoint`` (the experts as a ``lax.scan`` over the
stacked weights, whose gradient then comes out stacked and not as eight
padded copies), so the float32 pass of two 4096-token rows, and the
control's, fit beside the harness's copies of the parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST


def _sizes(cfg) -> dict:
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
        "dense": cfg["intermediate_size"], "expert": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "held": cfg["n_routed_experts"], "first": cfg["held_first"],
        "router": cfg["n_routed_experts_published"], "k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"], "first_dense": cfg["first_k_dense_replace"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
    }


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    d, h = z["d"], z["heads"]
    tree = {"embed": {"embedding": mat(z["vocab"], d)}}
    for i in range(z["layers"]):
        block = {
            "ln1": norm(d),
            "attn": {"query": {"kernel": mat(d, h * (z["dn"] + z["dr"]))},
                     "kv_a": {"kernel": mat(d, z["rank"] + z["dr"])},
                     "kv_norm": norm(z["rank"]),
                     "kv_b": {"kernel": mat(z["rank"], h * (z["dn"] + z["dv"]))},
                     "attn_out": {"kernel": mat(h * z["dv"], d)}},
            "ln2": norm(d),
        }
        if i < z["first_dense"]:
            block["mlp"] = {"gate": {"kernel": mat(d, z["dense"])},
                            "in": {"kernel": mat(d, z["dense"])},
                            "out": {"kernel": mat(z["dense"], d)}}
        else:
            block["moe"] = {
                "router": {"kernel": mat(d, z["router"])},
                "w_gate": mat(z["held"], d, z["expert"]),
                "w_in": mat(z["held"], d, z["expert"]),
                "w_out": mat(z["held"], z["expert"], d),
                "shared_gate": {"kernel": mat(d, z["shared"])},
                "shared_in": {"kernel": mat(d, z["shared"])},
                "shared_out": {"kernel": mat(z["shared"], d)},
            }
        tree[f"block{i}"] = block
    tree["ln_f"] = norm(d)
    tree["lm_head"] = {"kernel": mat(d, z["vocab"])}
    return tree


# -- rotary positions with YaRN -------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """(rotary width / 2,) inverse frequencies: ``f_i / factor`` below the
    correction range, ``f_i`` above it, a linear ramp between."""
    dim, theta, r = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dim_of(turns):
        return (dim * math.log(r["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return f / r["factor"] * ramp + f * (1 - ramp)


def softmax_scale(cfg) -> float:
    r = cfg["rope_scaling"]
    m = yarn_mscale(r["factor"], r["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """Rotate-half rotary on (B, T, H, rotary width)."""
    r = cfg["rope_scaling"]
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * yarn_inv_freq(cfg)
    ang = np.concatenate([ang, ang], -1)
    m = yarn_mscale(r["factor"], r["mscale"]) / yarn_mscale(r["factor"], r["mscale_all_dim"])
    cos = jnp.asarray(np.cos(ang) * m, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * m, jnp.float32)[None, :, None, :]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


# -- layers -----------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _gated(x, gate, up, down, wrap):
    return _mm(jax.nn.silu(_mm(x, gate, wrap)) * _mm(x, up, wrap), down, wrap)


def _head(q, k, v, scale, wrap):
    """One head's causal attention: (B, T, dq), (B, T, dq), (B, T, dv)."""
    t = q.shape[1]
    s = wrap(lambda a, b: jnp.einsum("bqd,bkd->bqk", a, b, precision=_HI))(q, k) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return wrap(lambda a, b: jnp.einsum("bqk,bkd->bqd", a, b, precision=_HI))(p, v)


def _mla(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    b, t, _ = x.shape
    h, dn, dr, dv = z["heads"], z["dn"], z["dr"], z["dv"]
    q = _mm(x, p["query"]["kernel"], wrap).reshape(b, t, h, dn + dr)
    kva = _mm(x, p["kv_a"]["kernel"], wrap)
    c = _rms(kva[..., :z["rank"]], p["kv_norm"], z["eps"])
    kvb = _mm(c, p["kv_b"]["kernel"], wrap).reshape(b, t, h, dn + dv)
    k_rope = _rope(kva[..., None, z["rank"]:], cfg)[:, :, 0]
    q_rope = _rope(q[..., dn:], cfg)
    head = jax.checkpoint(_head, static_argnums=(3, 4)) if remat else _head
    outs = []
    for i in range(h):
        qi = jnp.concatenate([q[:, :, i, :dn], q_rope[:, :, i]], -1)
        ki = jnp.concatenate([kvb[:, :, i, :dn], k_rope], -1)
        outs.append(head(qi, ki, kvb[:, :, i, dn:], softmax_scale(cfg), wrap))
    return _mm(jnp.concatenate(outs, -1), p["attn_out"]["kernel"], wrap)


def _moe(p, x, cfg, wrap, remat):
    """-> (this chip's part of the sparse FFN's output, the balance loss)."""
    z = _sizes(cfg)
    b, t, d = x.shape
    e, k = z["router"], z["k"]
    # the router is float32 in the program too; HIGHEST, and not wrapped: the
    # control lowers the precision of the matmuls the configuration runs in
    # bfloat16, and the program's router is not one of them
    probs = jax.nn.softmax(jnp.dot(x, p["router"]["kernel"], precision=_HI), -1)
    top_p, top_i = lax.top_k(probs, k)                      # (B, T, k), greedy
    expert = jax.checkpoint(_gated, static_argnums=(4,)) if remat else _gated
    y = _gated(x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
               p["shared_out"]["kernel"], wrap)

    def add_expert(y, held):
        j, w_gate, w_in, w_out = held
        gate = jnp.sum(jnp.where(top_i == z["first"] + j, top_p, 0.0), -1)   # (B, T)
        return y + gate[..., None] * expert(x, w_gate, w_in, w_out, wrap), None

    y, _ = lax.scan(add_expert, y, (jnp.arange(z["held"]), p["w_gate"], p["w_in"], p["w_out"]))
    chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=2)    # (B, T, E)
    f = jnp.sum(chosen, axis=1) * (e / (k * t))
    aux = cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), -1))
    return y, aux


def _block(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    x = x + _mla(p["attn"], _rms(x, p["ln1"], z["eps"]), cfg, wrap, remat)
    h = _rms(x, p["ln2"], z["eps"])
    if "mlp" in p:
        m = p["mlp"]
        dense = jax.checkpoint(_gated, static_argnums=(4,)) if remat else _gated
        return x + dense(h, m["gate"]["kernel"], m["in"]["kernel"], m["out"]["kernel"], wrap), 0.0
    y, aux = _moe(p["moe"], h, cfg, wrap, remat)
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


def logits(params, tokens, cfg, wrap=_plain, remat=True):
    """(N, T) int tokens -> ((N, T, vocab) float32 logits, balance loss)."""
    x = params["embed"]["embedding"][tokens]
    block = jax.checkpoint(_block_static, static_argnums=(2, 3, 4)) if remat else _block_static
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, a = block(params[f"block{i}"], x, _Static(cfg), wrap, remat)
        aux = aux + a
    x = _rms(x, params["ln_f"], cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], wrap), aux


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over the vocabulary slice; the gradient
    is that of cross entropy + balance loss (see the module's docstring)."""
    lg, aux = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1))
    return ce + (aux - lax.stop_gradient(aux))
