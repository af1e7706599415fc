"""Plain reference for ``qwen3-next-80b-a3b-instruct``: one chip's share of the
model and its next-token objective, float32 ``jax.numpy``, nothing of the
program imported.

Written from the source's ``config.json`` (``model_type`` ``qwen3_next``) and
the equations of ISSUE 43, which are those of the published modelling code
(``Qwen3NextGatedDeltaNet`` with ``torch_recurrent_gated_delta_rule``,
``Qwen3NextAttention``, ``Qwen3NextSparseMoeBlock``, ``Qwen3NextRMSNorm``):

* a layer ``l`` of kind ``t_l`` (``layer_types``, the published list: three
  ``linear_attention`` then one ``full_attention``; this chip runs the
  published layers ``layers_held``), pre-norm, no bias anywhere: ``h = x +
  Mixer_t(norm(x))``, ``y = h + MoE(norm(h))``; every layer is sparse; a final
  norm and an untied head; mean next-token cross entropy over the vocabulary
  slice.  ``norm(x) = x / rms(x) * (1 + w)`` (``w`` seeded 0) everywhere but
  inside the linear-attention layer;
* ``linear_attention`` (Gated DeltaNet; 16 key heads, 32 value heads, all 128
  wide): ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba`` (the columns in
  that order, every part's heads side by side: the program's layout; the
  published one groups them by key head, which matters to loading a
  checkpoint and not to the mathematics); ``[q | k | v]`` through a depthwise
  causal convolution of ``linear_conv_kernel_dim`` taps (``c_t = sum_j w_j
  u_{t-3+j}``, zeros before the row) and SiLU; ``q`` and ``k`` L2-normalised
  over their 128 dimensions (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` times
  ``128^-1/2``; key head ``h // 2`` serves value head ``h``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; then position
  by position, from ``S = 0`` (128 x 128 a value head, float32)::

      S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

  ``y = o / rms(o) * w * silu(z)`` a head (``w`` seeded 1), and ``W_out``;
* ``full_attention``: ``[q | gate] = u W_q`` (16 heads of 256 each; all
  queries, then all gates), ``k``, ``v`` of 2 heads of 256; ``norm`` on every
  query and key head; the rotary turn (rotate-half, theta 1e7) over the first
  ``partial_rotary_factor * 256 = 64`` dimensions of a head, ``i`` with ``i +
  32``, the other 192 left alone; query head ``j`` uses key/value head ``j //
  8``; causal softmax of ``q.k / 16``; ``out * sigmoid(gate)``; ``W_o``;
* MoE: ``p = softmax(W_r u)`` over all 512 router outputs, the 10 largest,
  their gates divided by their sum; ``y = sum over the chosen experts e held
  here of g_e E_e(u) + sigmoid(u w_sg) * E_shared(u)``, every expert
  ``W_down (silu(W_gate u) * W_up u)`` at width 512.  This chip holds
  ``num_experts`` of the router's ``num_experts_published`` (experts
  ``held_first ..``); what the others would add is left out, as in the
  program.  Every held expert is computed on every position and masked by
  membership in its top 10: no sort, no grouped product.  Balance loss, the
  Switch form over all 512 outputs and all positions of the batch.

Departures, each under ``assumed`` in the configuration's file: no MTP head,
``router_aux_loss_coef``, ``A_log`` at the quantiles of its published
distribution, the seeded weights' scales.

The Trainer reports the data loss and differentiates data loss + balance
loss.  ``loss`` returns ``data + (aux - stop_gradient(aux))``.

``wrap`` decorates every matmul the configuration runs in bfloat16: the
projections, the two of attention, each expert's, and the rule's three
products of a position (``S^T k``, the rank-one write, ``S^T q``: the program
computes their chunked equivalents on bfloat16 operands, the state among
them).  The router, the convolution's taps (a float32 sum in the program),
the decays and the norms are float32 in the program too and are not wrapped.
Memory: the recurrence keeps one state a chunk of ``RULE_CHUNK`` positions
and runs a chunk again in the backward pass (``jax.checkpoint``); attention
takes its heads one at a time, the experts come one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
#: positions between two states the recurrence's backward pass keeps
RULE_CHUNK = 64


def _sizes(cfg) -> dict:
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "hk": cfg["linear_num_key_heads"], "hv": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "taps": cfg["linear_conv_kernel_dim"],
        "expert": cfg["moe_intermediate_size"], "shared": cfg["shared_expert_intermediate_size"],
        "held": cfg["num_experts"], "first": cfg["held_first"],
        "router": cfg["num_experts_published"], "k": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
    }


def layer_types(cfg) -> list:
    """The kinds of the published layers this chip runs (``layers_held``): the
    published rule, layer ``i`` is ``full_attention`` iff ``(i + 1) %
    full_attention_interval == 0``."""
    every = cfg["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in cfg["layers_held"]]


def a_log_values(heads: int) -> np.ndarray:
    """``A_log`` a value head: the source seeds ``log(uniform(0, 16))``; here
    the ``heads`` quantiles of that distribution, the same in every seed."""
    return np.log(16.0 * (np.arange(heads) + 0.5) / heads).astype(np.float32)


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "zeros")}  # noqa: E731   the (1 + w) form
    d, hd = z["d"], z["hd"]
    keys, values = z["hk"] * z["dk"], z["hv"] * z["dv"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i, kind in enumerate(layer_types(cfg)):
        block = {"ln1": norm(d), "ln2": norm(d)}
        if kind == "linear_attention":
            block["deltanet"] = {
                "in_proj_qkvz": {"kernel": mat(d, 2 * keys + 2 * values)},
                "in_proj_ba": {"kernel": mat(d, 2 * z["hv"])},
                "conv": ((z["taps"], 2 * keys + values), ("normal", cfg["conv_init_std"])),
                "A_log": ((z["hv"],), ("const", a_log_values(z["hv"]))),
                "dt_bias": ((z["hv"],), "ones"),
                "norm": ((z["dv"],), "ones"),
                "out_proj": {"kernel": mat(values, d)}}
        else:
            block["attn"] = {
                "query": {"kernel": mat(d, 2 * z["heads"] * hd)},
                "key": {"kernel": mat(d, z["kv"] * hd)},
                "value": {"kernel": mat(d, z["kv"] * hd)},
                "q_norm": norm(hd), "k_norm": norm(hd),
                "attn_out": {"kernel": mat(z["heads"] * hd, d)}}
        block["moe"] = {
            "router": {"kernel": mat(d, z["router"])},
            "w_gate": mat(z["held"], d, z["expert"]),
            "w_in": mat(z["held"], d, z["expert"]),
            "w_out": mat(z["held"], z["expert"], d),
            "shared_gate": {"kernel": mat(d, z["shared"])},
            "shared_in": {"kernel": mat(d, z["shared"])},
            "shared_out": {"kernel": mat(z["shared"], d)},
            "shared_expert_gate": {"kernel": mat(d, 1)}}
        tree[f"block{i}"] = block
    tree["ln_f"] = norm(d)
    tree["lm_head"] = {"kernel": mat(d, z["vocab"])}
    return tree


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1.0 + p["scale"])


# .. the gated delta rule, position by position .................................
def _state_times(s, x):
    """``S^T x`` a head: (N, H, dk, dv), (N, H, dk) -> (N, H, dv)."""
    return jnp.einsum("nhkv,nhk->nhv", s, x, precision=_HI)


def _rank_one(k, e):
    return jnp.einsum("nhk,nhv->nhkv", k, e, precision=_HI)


def _positions(s, xs, wrap):
    """A chunk of positions of the recurrence: xs = (q, k, v, g, beta), the
    positions in front."""
    def step(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None, None] * s
        err = v - wrap(_state_times)(s, k)
        s = s + wrap(_rank_one)(k, beta[..., None] * err)
        return s, wrap(_state_times)(s, q)

    return lax.scan(step, s, xs)


def delta_rule(q, k, v, g, beta, wrap=_plain, remat=True):
    """(N, T, H, dk) q, k; (N, T, H, dv) v; (N, T, H) g, beta -> (N, T, H, dv)."""
    n, t, h, dk = q.shape
    chunk = math.gcd(t, RULE_CHUNK)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((t // chunk, chunk) + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, g, beta))
    body = jax.checkpoint(_positions, static_argnums=(2,)) if remat else _positions
    s0 = jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32)
    _, out = lax.scan(lambda s, x: body(s, x, wrap), s0, xs)
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def _taps(u, w):
    """``c_t = sum_j w_j u_{t-(K-1)+j}`` of (N, T, D) under (K, D) taps."""
    k, t = w.shape[0], u.shape[1]
    moved = lambda s: jnp.pad(u, ((0, 0), (s, 0), (0, 0)))[:, :t]  # noqa: E731
    return sum(w[j] * moved(k - 1 - j) for j in range(k))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _linear(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    n, t, _ = x.shape
    hk, hv, dk, dv = z["hk"], z["hv"], z["dk"], z["dv"]
    keys, values = hk * dk, hv * dv
    qkvz = _mm(x, p["in_proj_qkvz"]["kernel"], wrap)
    ba = _mm(x, p["in_proj_ba"]["kernel"], wrap)
    qkv = jax.nn.silu(_taps(qkvz[..., :2 * keys + values], p["conv"]))
    heads = lambda a, h: a.reshape(n, t, h, -1)  # noqa: E731
    q = _unit(heads(qkv[..., :keys], hk)) * dk ** -0.5
    k = _unit(heads(qkv[..., keys:2 * keys], hk))
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    v = heads(qkv[..., 2 * keys:], hv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, wrap, remat)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + z["eps"]) * p["norm"]
    y = o * jax.nn.silu(heads(qkvz[..., 2 * keys + values:], hv))
    return _mm(y.reshape(n, t, values), p["out_proj"]["kernel"], wrap)


# .. gated attention with partial rotary ........................................
def tables(length: int, rot: int, theta: float):
    """(cos, sin), each (length, rot) float32, rotate-half convention."""
    f = float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(length, dtype=np.float64)[:, None] * f
    ang = np.concatenate([ang, ang], -1)
    return jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(np.sin(ang), jnp.float32)


def _turn(x, cos, sin):
    """The rotary turn over the first ``rot`` dimensions of (N, T, H, width)."""
    rot = cos.shape[-1]
    a, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-a[..., rot // 2:], a[..., :rot // 2]], -1)
    turned = a * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([turned, rest], -1)


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
    qg = _mm(x, p["query"]["kernel"], wrap)
    q, gate = qg[..., :h * hd].reshape(n, t, h, hd), qg[..., h * hd:]
    k = _mm(x, p["key"]["kernel"], wrap).reshape(n, t, kv, hd)
    v = _mm(x, p["value"]["kernel"], wrap).reshape(n, t, kv, hd)
    cos, sin = tables(t, z["rot"], cfg["rope_theta"])
    q = _turn(_norm(q, p["q_norm"], z["eps"]), cos, sin)
    k = _turn(_norm(k, p["k_norm"], z["eps"]), cos, sin)
    head = jax.checkpoint(_head, static_argnums=(3,)) if remat else _head

    def group(qkv):
        """The h / kv query heads that share one key/value head, one by one."""
        qs, kg, vg = qkv                         # (h/kv, N, T, hd), (N, T, hd) x 2
        return lax.map(lambda one: head(one, kg, vg, wrap), qs)

    if remat:
        group = jax.checkpoint(group)
    q = jnp.transpose(q.reshape(n, t, kv, h // kv, hd), (2, 3, 0, 1, 4))
    out = lax.map(group, (q, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    out = jnp.transpose(out, (2, 3, 0, 1, 4)).reshape(n, t, h * hd)   # (kv, h/kv, N, T, hd)
    return _mm(out * jax.nn.sigmoid(gate), p["attn_out"]["kernel"], wrap)


# .. the expert layer ...........................................................
def _gated(x, gate, up, down, wrap):
    return _mm(jax.nn.silu(_mm(x, gate, wrap)) * _mm(x, up, wrap), down, wrap)


def route(p, x, cfg):
    """-> ((N, T, 512) probabilities, (N, T, k) gates, (N, T, k) experts)."""
    # the router is float32 in the program too: HIGHEST, and not wrapped
    probs = jax.nn.softmax(jnp.dot(x, p["router"]["kernel"], precision=_HI), -1)
    top_p, top_i = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return probs, top_p, top_i


def shared_part(p, x, wrap):
    """``sigmoid(u w_sg) * E_shared(u)``: every chip's rows pass through it once."""
    y = _gated(x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
               p["shared_out"]["kernel"], wrap)
    return jax.nn.sigmoid(_mm(x, p["shared_expert_gate"]["kernel"], wrap)) * y


def _weighed(x, gate, w_gate, w_in, w_out, wrap):
    """``g_e E_e(u)``: one expert's part, weighed a position."""
    return gate[..., None] * _gated(x, w_gate, w_in, w_out, wrap)


def routed_part(p, x, cfg, first: int, top_p, top_i, wrap=_plain, remat=False):
    """``sum g_e E_e(u)`` over the experts ``first ..`` that ``p`` holds."""
    # under remat an expert's backward keeps its gates alone, not its output
    expert = jax.checkpoint(_weighed, static_argnums=(5,)) if remat else _weighed

    def add_expert(y, held):
        j, w_gate, w_in, w_out = held
        gate = jnp.sum(jnp.where(top_i == first + j, top_p, 0.0), -1)    # (N, T)
        return y + expert(x, gate, w_gate, w_in, w_out, wrap), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(p["w_in"].shape[0]), p["w_gate"], p["w_in"], p["w_out"]))
    return y


def _moe(p, x, cfg, wrap, remat):
    """-> (this chip's part of the expert layer's output, the balance loss)."""
    e = cfg["num_experts_published"]
    probs, top_p, top_i = route(p, x, cfg)
    y = routed_part(p, x, cfg, cfg["held_first"], top_p, top_i, wrap, remat)
    first = jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32)
    aux = cfg["router_aux_loss_coef"] * e * jnp.sum(
        jnp.mean(first, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return y + shared_part(p, x, wrap), aux


def _block(p, x, kind, cfg, wrap, remat):
    eps = cfg["rms_norm_eps"]
    u = _norm(x, p["ln1"], eps)
    x = x + (_linear(p["deltanet"], u, cfg, wrap, remat) if kind == "linear_attention"
             else _attn(p["attn"], u, cfg, wrap, remat))
    y, aux = _moe(p["moe"], _norm(x, p["ln2"], eps), cfg, wrap, remat)
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
    x = _norm(x, params["ln_f"], cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], wrap), aux


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over the vocabulary slice; the gradient
    is that of it + the balance loss (see the module's docstring)."""
    lg, aux = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    data = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    return data + (aux - lax.stop_gradient(aux))
