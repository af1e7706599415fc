"""Plain reference for ``lfm2-8b-a1b``: one chip's share of the model,
float32 ``jax.numpy``, nothing of the program imported.

Written from the source's ``config.json`` (``model_type`` ``lfm2_moe``) and
the equations of ISSUE 33, which are those of the published modelling code
(``Lfm2MoeShortConv``, ``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``):

* a layer, pre-norm: ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``;
  ``layer_types`` names each layer's mixer; the first ``num_dense_layers``
  layers carry a dense SiLU-gated MLP, the others the expert layer; a final
  RMSNorm (the source's ``embedding_norm``) and an untied head; mean
  next-token cross entropy over the vocabulary slice;
* mixer ``conv``, on ``u`` of width ``D``, ``K = conv_L_cache`` taps, no bias,
  no activation: ``[B | C | h] = u W_in``; ``z = B * h``; ``c_t = sum_{j=0..K-1}
  w_j * z_{t-(K-1)+j}`` with ``z_t = 0`` for ``t < 0`` (depthwise, causal, each
  row of the batch on its own), written as the sum of ``K`` shifted products;
  ``y = (C * c) W_out``;
* mixer ``full_attention``: ``q = W_q u`` (32 heads of 64), ``k = W_k u``,
  ``v = W_v u`` (8 heads of 64); every query head and every key head
  RMS-normalised over its 64 dimensions with a learned scale; rotary over all
  64 dimensions (rotate-half, theta 1e6); query head ``j`` uses key/value head
  ``j // 4``; causal scores ``q.k / sqrt(64)``, softmax, ``W_o`` of the
  concatenated heads;
* the expert layer: ``s = sigmoid(u W_r)`` over all 32 router outputs; the 4
  largest of ``s + b`` (``b``: the selection bias, a leaf of 32); their weights
  ``g = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor``; ``y = sum over
  the chosen experts e held here of g_e W_out_e (silu(W_gate_e u) * W_in_e
  u)``.  This chip holds ``num_experts`` of the router's
  ``num_experts_published`` (experts ``held_first ..``); what the others would
  add is left out, as in the program.  Every held expert is computed on every
  position and masked by membership in its top 4: no sort, no grouped product.
  No shared expert, no balance loss.

Departures, each under ``assumed`` in the configuration's file: the untied
head, the bias frozen (it enters ``top_k``'s argument only, so its gradient
is zero and SGD leaves it alone; the source's update rule is not in its
config), no balance loss, the seeded weights' scales.

``wrap`` decorates every matmul the configuration runs in bfloat16, the two
of attention and each expert's among them, AND the taps' sum as one bilinear
operation of ``(z, w)``: the program computes it from operands that were
rounded to bfloat16 on their way in (``B``, ``h`` and the taps), so the
control rounds ``z`` and ``w``.  The gates ``B * h`` and ``C * c`` are
elementwise in float32 in the program and are not wrapped.  The router is
float32 in the program too and is not wrapped.  Memory: a row's float32
scores are 64 MiB a head, so the heads are taken one at a time under
``jax.checkpoint``, a key/value group at a time (``lax.map``), and the experts
one at a time as a ``lax.scan`` over the stacked weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST


def _sizes(cfg) -> dict:
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
        "taps": cfg["conv_L_cache"], "dense": cfg["intermediate_size"],
        "expert": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
        "first": cfg["held_first"], "router": cfg["num_experts_published"],
        "k": cfg["num_experts_per_tok"], "layers": cfg["num_hidden_layers"],
        "first_dense": cfg["num_dense_layers"], "vocab": cfg["vocab_size"],
        "eps": cfg["norm_eps"],
    }


def layer_types(cfg) -> list:
    """The mixers of the published layers this chip runs (``layers_held``),
    out of the published list."""
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    d, hd = z["d"], z["hd"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i, kind in enumerate(layer_types(cfg)):
        block = {"ln1": norm(d), "ln2": norm(d)}
        if kind == "conv":
            block["conv"] = {"in_proj": {"kernel": mat(d, 3 * d)},
                             "w": ((z["taps"], d), ("normal", cfg["conv_init_std"])),
                             "out_proj": {"kernel": mat(d, d)}}
        else:
            block["attn"] = {"query": {"kernel": mat(d, z["heads"] * hd)},
                             "key": {"kernel": mat(d, z["kv"] * hd)},
                             "value": {"kernel": mat(d, z["kv"] * hd)},
                             "q_norm": norm(hd), "k_norm": norm(hd),
                             "attn_out": {"kernel": mat(z["heads"] * hd, d)}}
        if i < z["first_dense"]:
            block["mlp"] = {"gate": {"kernel": mat(d, z["dense"])},
                            "in": {"kernel": mat(d, z["dense"])},
                            "out": {"kernel": mat(z["dense"], d)}}
        else:
            block["moe"] = {"router": {"kernel": mat(d, z["router"])},
                            "expert_bias": ((z["router"],),
                                            ("normal", cfg["expert_bias_init_std"])),
                            "w_gate": mat(z["held"], d, z["expert"]),
                            "w_in": mat(z["held"], d, z["expert"]),
                            "w_out": mat(z["held"], z["expert"], d)}
        tree[f"block{i}"] = block
    tree["ln_f"] = norm(d)
    tree["lm_head"] = {"kernel": mat(d, z["vocab"])}
    return tree


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _gated(x, gate, up, down, wrap):
    return _mm(jax.nn.silu(_mm(x, gate, wrap)) * _mm(x, up, wrap), down, wrap)


def _taps(z, w):
    """``c_t = sum_j w_j z_{t-(K-1)+j}`` of (N, T, D) under (K, D) taps: the
    sum of K copies of ``z``, each moved down the sequence behind zeros."""
    k, t = w.shape[0], z.shape[1]
    moved = lambda s: jnp.pad(z, ((0, 0), (s, 0), (0, 0)))[:, :t]  # noqa: E731
    return sum(w[j] * moved(k - 1 - j) for j in range(k))


def _conv(p, x, cfg, wrap):
    d = x.shape[-1]
    bch = _mm(x, p["in_proj"]["kernel"], wrap)
    b, c, h = bch[..., :d], bch[..., d:2 * d], bch[..., 2 * d:]
    return _mm(c * wrap(_taps)(b * h, p["w"]), p["out_proj"]["kernel"], wrap)


def _rope(x, theta):
    """Rotate-half rotary over the whole width of (N, T, H, width)."""
    t, dim = x.shape[1], x.shape[-1]
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(t, dtype=np.float64)[:, None] * f
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _head(q, k, v, wrap):
    """One head's causal attention: (N, T, hd) each."""
    t = q.shape[1]
    s = wrap(lambda a, b: jnp.einsum("bqd,bkd->bqk", a, b, precision=_HI))(q, k)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s * q.shape[-1] ** -0.5, -jnp.inf), axis=-1)
    return wrap(lambda a, b: jnp.einsum("bqk,bkd->bqd", a, b, precision=_HI))(p, v)


def _attn(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    n, t, _ = x.shape
    h, kv, hd = z["heads"], z["kv"], z["hd"]
    q = _mm(x, p["query"]["kernel"], wrap).reshape(n, t, h, hd)
    k = _mm(x, p["key"]["kernel"], wrap).reshape(n, t, kv, hd)
    v = _mm(x, p["value"]["kernel"], wrap).reshape(n, t, kv, hd)
    q = _rope(_rms(q, p["q_norm"], z["eps"]), cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], z["eps"]), cfg["rope_theta"])
    head = jax.checkpoint(_head, static_argnums=(3,)) if remat else _head

    def group(qkv):
        """The h / kv query heads that share one key/value head."""
        qg, kg, vg = qkv                         # (N, T, h/kv, hd), (N, T, hd) x 2
        return jnp.stack([head(qg[:, :, j], kg, vg, wrap) for j in range(h // kv)], axis=2)

    if remat:
        group = jax.checkpoint(group)
    heads_first = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    out = lax.map(group, (heads_first(q.reshape(n, t, kv, h // kv, hd)),
                          heads_first(k), heads_first(v)))   # (kv, N, T, h/kv, hd)
    out = jnp.moveaxis(out, 0, 2).reshape(n, t, h * hd)
    return _mm(out, p["attn_out"]["kernel"], wrap)


def route(p, x, cfg):
    """-> ((N, T, k) weights, (N, T, k) experts) of the source's router."""
    z = _sizes(cfg)
    # the router is float32 in the program too: HIGHEST, and not wrapped
    s = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"], precision=_HI))
    _, top_i = lax.top_k(s + p["expert_bias"], z["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    return top_s * cfg["routed_scaling_factor"], top_i


def _moe(p, x, cfg, wrap, remat):
    """This chip's part of the expert layer's output."""
    z = _sizes(cfg)
    top_s, top_i = route(p, x, cfg)
    expert = jax.checkpoint(_gated, static_argnums=(4,)) if remat else _gated

    def add_expert(y, held):
        j, w_gate, w_in, w_out = held
        gate = jnp.sum(jnp.where(top_i == z["first"] + j, top_s, 0.0), -1)    # (N, T)
        return y + gate[..., None] * expert(x, w_gate, w_in, w_out, wrap), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(z["held"]), p["w_gate"], p["w_in"], p["w_out"]))
    return y


def _block(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    u = _rms(x, p["ln1"], z["eps"])
    x = x + (_conv(p["conv"], u, cfg, wrap) if "conv" in p
             else _attn(p["attn"], u, cfg, wrap, remat))
    u = _rms(x, p["ln2"], z["eps"])
    if "mlp" in p:
        m = p["mlp"]
        dense = jax.checkpoint(_gated, static_argnums=(4,)) if remat else _gated
        return x + dense(u, m["gate"]["kernel"], m["in"]["kernel"], m["out"]["kernel"], wrap)
    return x + _moe(p["moe"], u, cfg, wrap, remat)


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
    """(N, T) int tokens -> (N, T, vocab) float32 logits."""
    x = params["embed"]["embedding"][tokens]
    block = jax.checkpoint(_block_static, static_argnums=(2, 3, 4)) if remat else _block_static
    for i in range(cfg["num_hidden_layers"]):
        x = block(params[f"block{i}"], x, _Static(cfg), wrap, remat)
    x = _rms(x, params["ln_f"], cfg["norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], wrap)


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over the vocabulary slice."""
    logp = jax.nn.log_softmax(logits(params, inputs, cfg, wrap, remat), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1))
