"""Plain reference for ``mellum2-12b-a2.5b-instruct``: one chip's share of the
model and its next-token objective, float32 ``jax.numpy``, nothing of the
program imported.

Written from the source's ``config.json`` (``model_type`` ``mellum``: a
Qwen3-MoE-shaped decoder whose layers are of two kinds of attention) and the
equations of ISSUE 41:

* a layer ``l`` of kind ``t_l`` (``layer_types``, the published list; this chip
  runs the published layers ``layers_held``), pre-norm, no bias anywhere:
  ``h = x + Attn_t(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; every layer is
  sparse; a final RMSNorm and an untied head; mean next-token cross entropy
  over the vocabulary slice;
* Attn: ``q = W_q u`` (32 heads of 128), ``k = W_k u``, ``v = W_v u`` (4 heads
  of 128); every query head and every key head RMS-normalised over its 128
  dimensions with a learned scale; the rotary turn (rotate-half, all 128
  dimensions) with the tables of the layer's kind (``rope_parameters``):
  ``sliding_attention``: ``f_i = theta^(-2i/128)``, cos and sin of ``p f_i``;
  ``full_attention``: YaRN, ``f_i / factor`` blended into ``f_i`` by a linear
  ramp over the pairs between ``low = floor(dim_of(beta_fast))`` and ``high =
  ceil(dim_of(beta_slow))``, ``dim_of(n) = 128 ln(original / (2 pi n)) / (2 ln
  theta)`` (18 and 35 here), and cos and sin multiplied by ``attention_factor``.
  Query head ``j`` uses key/value head ``j // 8``; scores ``q.k / sqrt(128)``;
  query ``i`` sees key ``j`` iff ``j <= i`` (``full_attention``) or ``i -
  sliding_window < j <= i`` (``sliding_attention``: ``sliding_window`` keys, its
  own among them), the dense (L, L) mask of each kind built from ``arange``;
  softmax; ``W_o`` of the concatenated heads;
* MoE: ``p = softmax(W_r u)`` over all 64 router outputs, the 8 largest, their
  gates divided by their sum (``norm_topk_prob``); ``y = sum over the chosen
  experts e held here of g_e W_down_e (silu(W_gate_e u) * W_up_e u)``.  This
  chip holds ``num_experts`` of the router's ``num_experts_published`` (experts
  ``held_first ..``); what the others would add is left out, as in the program.
  Every held expert is computed on every position and masked by membership in
  its top 8: no sort, no grouped product.  No shared expert.  Balance loss, the
  Switch form over all 64 outputs and all positions of the batch: ``coef * 64 *
  sum_e (share of positions whose first choice is e) * (mean gate of e)``.

Departures, each under ``assumed`` in the configuration's file: the head norms
(the config has no key for them), no MTP head, ``router_aux_loss_coef``, the
seeded weights' scales.

The Trainer reports the data loss and differentiates data loss + balance
loss.  ``loss`` returns ``data + (aux - stop_gradient(aux))``: its value is the
data loss, its gradient that of the whole objective.

``wrap`` decorates every matmul the configuration runs in bfloat16, the two of
attention and each expert's among them (the control rounds their operands);
the router is float32 in the program too and is not wrapped.  Memory: a row's
float32 scores are 256 MiB a head, so the heads are taken one at a time under
``jax.checkpoint``, a key/value group at a time (``lax.map``), and the experts
one at a time as a ``lax.scan`` over the stacked weights.
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
        "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "expert": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
        "first": cfg["held_first"], "router": cfg["num_experts_published"],
        "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
    }


def layer_types(cfg) -> list:
    """The kinds of the published layers this chip runs (``layers_held``), out
    of the published list."""
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    d, hd = z["d"], z["hd"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i in range(len(layer_types(cfg))):
        # a window layer's leaves are a full layer's
        tree[f"block{i}"] = {
            "ln1": norm(d),
            "attn": {"query": {"kernel": mat(d, z["heads"] * hd)},
                     "key": {"kernel": mat(d, z["kv"] * hd)},
                     "value": {"kernel": mat(d, z["kv"] * hd)},
                     "q_norm": norm(hd), "k_norm": norm(hd),
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


# -- rotary tables and masks, by kind of layer ---------------------------------
def inv_freq(dim: int, rope: dict) -> np.ndarray:
    """(dim / 2,) float64 inverse frequencies of one ``rope_parameters`` entry."""
    theta = float(rope["rope_theta"])
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return f
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no equations for rope_type {rope['rope_type']!r}")

    def dim_of(turns):
        return (dim * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return f / rope["factor"] * ramp + f * (1 - ramp)


def tables(length: int, dim: int, rope: dict):
    """(cos, sin), each (length, dim) float32, rotate-half convention, times
    the entry's ``attention_factor`` (1 where it has none)."""
    ang = np.arange(length, dtype=np.float64)[:, None] * inv_freq(dim, rope)
    ang = np.concatenate([ang, ang], -1)
    m = float(rope.get("attention_factor", 1.0))
    return jnp.asarray(np.cos(ang) * m, jnp.float32), jnp.asarray(np.sin(ang) * m, jnp.float32)


def dense_mask(length: int, kind: str, window: int) -> np.ndarray:
    """(L, L) bool, [query, key]."""
    q, k = np.arange(length)[:, None], np.arange(length)[None, :]
    if kind == "full_attention":
        return k <= q
    if kind == "sliding_attention":
        return (k <= q) & (k > q - window)
    raise ValueError(f"no equations for a layer of kind {kind!r}")


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _turn(x, cos, sin):
    """Rotate-half rotary over the whole width of (N, T, H, width)."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def _head(q, k, v, kind, window, wrap):
    """One head under its layer's mask: (N, T, hd) each."""
    seen = jnp.asarray(dense_mask(q.shape[1], kind, window))
    s = wrap(lambda a, b: jnp.einsum("bqd,bkd->bqk", a, b, precision=_HI))(q, k)
    p = jax.nn.softmax(jnp.where(seen, s * q.shape[-1] ** -0.5, -jnp.inf), axis=-1)
    return wrap(lambda a, b: jnp.einsum("bqk,bkd->bqd", a, b, precision=_HI))(p, v)


def _attn(p, x, kind, cfg, wrap, remat):
    z = _sizes(cfg)
    n, t, _ = x.shape
    h, kv, hd = z["heads"], z["kv"], z["hd"]
    q = _mm(x, p["query"]["kernel"], wrap).reshape(n, t, h, hd)
    k = _mm(x, p["key"]["kernel"], wrap).reshape(n, t, kv, hd)
    v = _mm(x, p["value"]["kernel"], wrap).reshape(n, t, kv, hd)
    cos, sin = tables(t, hd, cfg["rope_parameters"][kind])
    q = _turn(_rms(q, p["q_norm"], z["eps"]), cos, sin)
    k = _turn(_rms(k, p["k_norm"], z["eps"]), cos, sin)
    head = jax.checkpoint(_head, static_argnums=(3, 4, 5)) if remat else _head

    def group(qkv):
        """The h / kv query heads that share one key/value head."""
        qg, kg, vg = qkv                         # (N, T, h/kv, hd), (N, T, hd) x 2
        return jnp.stack([head(qg[:, :, j], kg, vg, kind, cfg["sliding_window"], wrap)
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


def _block(p, x, kind, cfg, wrap, remat):
    z = _sizes(cfg)
    x = x + _attn(p["attn"], _rms(x, p["ln1"], z["eps"]), kind, cfg, wrap, remat)
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


def _block_static(p, x, kind, static, wrap, remat):
    return _block(p, x, kind, static.cfg, wrap, remat)


def logits(params, inputs, cfg, wrap=_plain, remat=True):
    """(N, L) tokens -> ((N, L, vocab) float32 logits, the balance loss)."""
    x = params["embed"]["embedding"][inputs]
    block = (jax.checkpoint(_block_static, static_argnums=(2, 3, 4, 5))
             if remat else _block_static)
    aux = 0.0
    for i, kind in enumerate(layer_types(cfg)):
        x, a = block(params[f"block{i}"], x, kind, _Static(cfg), wrap, remat)
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
