"""Plain reference for ``kimi-linear-48b-a3b-instruct``: one chip's share of the
model and its next-token objective, float32 ``jax.numpy``, nothing of the
program imported.

Written from the source's ``config.json`` (``model_type`` ``kimi_linear``) and
the equations of ISSUE 50, which are those of the family's released code (Kimi
Delta Attention, latent attention with ``mla_use_nope``, the sigmoid router):

* a published layer ``l`` (1-based, as ``linear_attn_config`` counts them; this
  chip runs ``layers_held``) is ``kda`` if ``l`` is in ``kda_layers`` and latent
  attention if it is in ``full_attn_layers``; pre-norm, no bias anywhere: ``h =
  x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; layers ``l <=
  first_k_dense_replace`` take a SiLU-gated MLP of ``intermediate_size``, the
  others the expert layer; a final RMSNorm and an untied head; mean next-token
  cross entropy over the vocabulary slice.  No position encoding anywhere;
* ``kda`` (32 heads, d_k = d_v = 128): ``[q | k | v] = u W_qkv``, each through a
  depthwise causal convolution of ``short_conv_kernel_size`` taps (``c_t =
  sum_j w_j u_{t-3+j}``, zeros before the row) and SiLU; ``q`` and ``k``
  L2-normalised over a head (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` times
  ``128^-1/2``; ``g = -exp(A_log[h]) * softplus((u W_fa) W_fb + dt_bias)``, a
  number a position, head AND key channel; ``beta = sigmoid(u W_b)`` a head;
  then position by position, from ``S = 0`` (128 x 128 a head, float32)::

      S <- diag(exp(g_t)) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

  (``exp(g_t)`` scales the state's ROWS, one factor a key channel); ``y = o /
  rms(o) * w * sigmoid((u W_ga) W_gb)`` a head (``w`` seeded 1), and ``W_out``;
* latent attention without positions: ``q = u W_q`` (32 heads of 128 + 64);
  ``[c | k_r] = u W_kva`` (512 + 64); ``[k_n | v]_h = RMSNorm(c) W_kvb``; ``k_h
  = [k_n,h | k_r]``, ``k_r`` shared by all heads, NOTHING rotated; causal
  softmax of ``q.k / sqrt(192)``; ``W_o`` of the concatenated heads;
* the expert layer: ``s = sigmoid(u W_r)`` over all 256 router outputs; the 8
  largest of ``s + b`` (``b``: the selection bias, a frozen leaf); weights
  ``s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor``; ``y = sum over the
  chosen experts e held here of w_e E_e(u) + E_shared(u)``, every expert ``W_out
  (silu(W_gate u) * W_in u)`` at width 1024.  This chip holds ``num_experts`` of
  the router's ``num_experts_published`` (experts ``held_first ..``); what the
  others would add is left out, as in the program.  Every held expert is
  computed on every position and masked by membership in its top 8: no sort,
  no grouped product.  No balance loss.

Departures, each under ``assumed`` in the configuration's file: the rank of the
two low-rank pairs, ``A_log`` at the quantiles of its published distribution
and ``dt_bias`` a constant, the bias frozen, the seeded weights' scales.  The
program divides the chosen scores by their sum + 1e-6, this file by the
published + 1e-20: at sums of about 4 the two differ by a rounding of float32.

``wrap`` decorates every matmul the configuration runs in bfloat16: the
projections (the low-rank pairs' among them), the two of attention, each
expert's, and the rule's three products of a position (``S^T k``, the rank-one
write, ``S^T q``: the program computes their chunked equivalents on bfloat16
operands, the state among them).  The router, the convolution's taps (a
float32 sum in the program), the decays and the norms are float32 in the
program too and are not wrapped.  Memory (the harness holds the parameters six
times beside this file's temporaries): every block under ``jax.checkpoint``;
the recurrence keeps one state a chunk of ``RULE_CHUNK`` positions and runs a
chunk again in the backward pass; attention takes its heads one at a time, the
experts come one at a time; the dense MLP, and the head with its
log-softmax, take ``ROWS`` rows at a time and compute them again.
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
#: rows the dense MLP and the head take at a time
ROWS = 512


def _sizes(cfg) -> dict:
    la = cfg["linear_attn_config"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
        "kh": la["num_heads"], "kd": la["head_dim"], "taps": la["short_conv_kernel_size"],
        "low": cfg["kda_low_rank"],
        "dense": cfg["intermediate_size"], "expert": cfg["moe_intermediate_size"],
        "shared": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        "held": cfg["num_experts"], "first": cfg["held_first"],
        "router": cfg["num_experts_published"], "k": cfg["num_experts_per_token"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
    }


def layer_types(cfg) -> list:
    """The mixers of the published layers this chip runs (``layers_held``,
    1-based as the config's own lists)."""
    la = cfg["linear_attn_config"]
    kinds = []
    for layer in cfg["layers_held"]:
        if layer not in la["kda_layers"] + la["full_attn_layers"]:
            raise ValueError(f"published layer {layer} is in neither list")
        kinds.append("kda" if layer in la["kda_layers"] else "full_attention")
    return kinds


def is_dense(cfg, layer: int) -> bool:
    """Published layer ``layer`` (1-based) carries the dense MLP."""
    return layer <= cfg["first_k_dense_replace"]


def a_log_values(heads: int) -> np.ndarray:
    """``A_log`` a head: the source seeds ``log(uniform(1, 16))``; here the
    ``heads`` quantiles of that distribution, the same in every seed."""
    return np.log(1.0 + 15.0 * (np.arange(heads) + 0.5) / heads).astype(np.float32)


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), cfg["matrix_init_std"]
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    kernel = lambda *shape: {"kernel": mat(*shape)}  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    d, h = z["d"], z["heads"]
    width = z["kh"] * z["kd"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i, (layer, kind) in enumerate(zip(cfg["layers_held"], layer_types(cfg))):
        block = {"ln1": norm(d), "ln2": norm(d)}
        if kind == "kda":
            block["kda"] = {
                "in_proj_qkv": kernel(d, 3 * width),
                "conv": ((z["taps"], 3 * width), ("normal", cfg["conv_init_std"])),
                "f_a": kernel(d, z["low"]), "f_b": kernel(z["low"], width),
                "A_log": ((z["kh"],), ("const", a_log_values(z["kh"]))),
                "dt_bias": ((width,), ("const", cfg["dt_bias_init"])),
                "b_proj": kernel(d, z["kh"]),
                "g_a": kernel(d, z["low"]), "g_b": kernel(z["low"], width),
                "norm": ((z["kd"],), "ones"),
                "out_proj": kernel(width, d)}
        else:
            block["attn"] = {
                "query": kernel(d, h * (z["dn"] + z["dr"])),
                "kv_a": kernel(d, z["rank"] + z["dr"]),
                "kv_norm": norm(z["rank"]),
                "kv_b": kernel(z["rank"], h * (z["dn"] + z["dv"])),
                "attn_out": {"kernel": ((h * z["dv"], d), ("normal", cfg["attn_out_init_std"]))}}
        if is_dense(cfg, layer):
            block["mlp"] = {"gate": kernel(d, z["dense"]), "in": kernel(d, z["dense"]),
                            "out": kernel(z["dense"], d)}
        else:
            block["moe"] = {
                "router": kernel(d, z["router"]),
                "expert_bias": ((z["router"],), ("normal", cfg["expert_bias_init_std"])),
                "w_gate": mat(z["held"], d, z["expert"]),
                "w_in": mat(z["held"], d, z["expert"]),
                "w_out": mat(z["held"], z["expert"], d),
                "shared_gate": kernel(d, z["shared"]), "shared_in": kernel(d, z["shared"]),
                "shared_out": kernel(z["shared"], d)}
        tree[f"block{i}"] = block
    tree["ln_f"] = norm(d)
    tree["lm_head"] = kernel(d, z["vocab"])
    return tree


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _row_blocks(t: int) -> int:
    return math.gcd(t, ROWS)


# .. the delta rule with a vector decay, position by position ...................
def _state_times(s, x):
    """``S^T x`` a head: (N, H, dk, dv), (N, H, dk) -> (N, H, dv)."""
    return jnp.einsum("nhkv,nhk->nhv", s, x, precision=_HI)


def _rank_one(k, e):
    return jnp.einsum("nhk,nhv->nhkv", k, e, precision=_HI)


def _positions(s, xs, wrap):
    """A chunk of positions of the recurrence: xs = (q, k, v, g, beta), the
    positions in front; ``g`` (.., N, H, dk) scales the state's rows."""
    def step(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None] * s
        err = v - wrap(_state_times)(s, k)
        s = s + wrap(_rank_one)(k, beta[..., None] * err)
        return s, wrap(_state_times)(s, q)

    return lax.scan(step, s, xs)


def delta_rule(q, k, v, g, beta, wrap=_plain, remat=True):
    """(N, T, H, dk) q, k, g; (N, T, H, dv) v; (N, T, H) beta -> (N, T, H, dv)."""
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


def decay(p, x, cfg, wrap=_plain):
    """``g`` (N, T, H, dk) of the layer input ``x``."""
    z = _sizes(cfg)
    f = _mm(_mm(x, p["f_a"]["kernel"], wrap), p["f_b"]["kernel"], wrap)
    g = jax.nn.softplus(f + p["dt_bias"]).reshape(x.shape[:2] + (z["kh"], z["kd"]))
    return -jnp.exp(p["A_log"])[:, None] * g


def output_gate(p, x, wrap=_plain):
    return jax.nn.sigmoid(_mm(_mm(x, p["g_a"]["kernel"], wrap), p["g_b"]["kernel"], wrap))


def _kda_part(w, taps, x, wrap):
    """One of q, k, v before the heads' norms: the projection's columns
    ``w``, their taps, SiLU."""
    return jax.nn.silu(_taps(_mm(x, w, wrap), taps))


def _kda_inputs(p, x, static, wrap, remat):
    """-> q, k, v, g, beta of the layer input ``x``; q, k and v one after
    another, so that a third of the fused projection's rows is alive at a time."""
    cfg = static.cfg
    z = _sizes(cfg)
    n, t, _ = x.shape
    h, dk = z["kh"], z["kd"]
    width = h * dk
    part = jax.checkpoint(_kda_part, static_argnums=(3,)) if remat else _kda_part
    q, k, v = (part(p["in_proj_qkv"]["kernel"][:, i * width:(i + 1) * width],
                    p["conv"][:, i * width:(i + 1) * width], x, wrap).reshape(n, t, h, dk)
               for i in range(3))
    beta = jax.nn.sigmoid(_mm(x, p["b_proj"]["kernel"], wrap))
    return _unit(q) * dk ** -0.5, _unit(k), v, decay(p, x, cfg, wrap), beta


def _kda_output(p, x, o, eps, wrap):
    """The gated norm on the rule's output, and ``W_out``."""
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * p["norm"]
    y = o.reshape(x.shape[:2] + (-1,)) * output_gate(p, x, wrap)
    return _mm(y, p["out_proj"]["kernel"], wrap)


def _kda(p, x, cfg, wrap, remat):
    # under remat the layer keeps what the rule reads and writes alone: what
    # lies before and behind it is computed again, each in its turn
    inputs = jax.checkpoint(_kda_inputs, static_argnums=(2, 3, 4)) if remat else _kda_inputs
    output = jax.checkpoint(_kda_output, static_argnums=(3, 4)) if remat else _kda_output
    q, k, v, g, beta = inputs(p, x, _Static(cfg), wrap, remat)
    o = delta_rule(q, k, v, g, beta, wrap, remat)
    return output(p, x, o, cfg["rms_norm_eps"], wrap)


# .. latent attention, no positions .............................................
def _head(q, k, v, scale, wrap):
    """One head's causal attention: (N, T, width) each."""
    t = q.shape[1]
    s = wrap(lambda a, b: jnp.einsum("bqd,bkd->bqk", a, b, precision=_HI))(q, k)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
    return wrap(lambda a, b: jnp.einsum("bqk,bkd->bqd", a, b, precision=_HI))(p, v)


def _mla(p, x, cfg, wrap, remat):
    z = _sizes(cfg)
    n, t, _ = x.shape
    h, dn, dr, dv = z["heads"], z["dn"], z["dr"], z["dv"]
    q = _mm(x, p["query"]["kernel"], wrap).reshape(n, t, h, dn + dr)
    kva = _mm(x, p["kv_a"]["kernel"], wrap)
    c = _norm(kva[..., :z["rank"]], p["kv_norm"], z["eps"])
    kvb = _mm(c, p["kv_b"]["kernel"], wrap).reshape(n, t, h, dn + dv)
    k_r = kva[..., z["rank"]:]                      # shared by all heads, not turned
    head = jax.checkpoint(_head, static_argnums=(3, 4)) if remat else _head

    def one(qkv):
        qh, kn, vh = qkv                            # (N, T, dn + dr), (N, T, dn), (N, T, dv)
        return head(qh, jnp.concatenate([kn, k_r], -1), vh, (dn + dr) ** -0.5, wrap)

    first = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    out = lax.map(one, (first(q), first(kvb[..., :dn]), first(kvb[..., dn:])))
    return _mm(jnp.moveaxis(out, 0, 2).reshape(n, t, h * dv), p["attn_out"]["kernel"], wrap)


# .. the expert layer ...........................................................
def _gated(x, gate, up, down, wrap):
    return _mm(jax.nn.silu(_mm(x, gate, wrap)) * _mm(x, up, wrap), down, wrap)


def route(p, x, cfg):
    """-> ((N, T, k) weights, (N, T, k) experts) of the source's router."""
    # the router is float32 in the program too: HIGHEST, and not wrapped
    s = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"], precision=_HI))
    _, top_i = lax.top_k(s + p["expert_bias"], cfg["num_experts_per_token"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg["moe_renormalize"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_s * cfg["routed_scaling_factor"], top_i


def shared_part(p, x, wrap=_plain):
    """``E_shared(u)``: every chip's rows pass through it once."""
    return _gated(x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                  p["shared_out"]["kernel"], wrap)


def _weighed(x, weight, w_gate, w_in, w_out, wrap):
    """``w_e E_e(u)``: one expert's part, weighed a position."""
    return weight[..., None] * _gated(x, w_gate, w_in, w_out, wrap)


def routed_part(p, x, first: int, top_s, top_i, wrap=_plain, remat=False):
    """``sum w_e E_e(u)`` over the experts ``first ..`` that ``p`` holds."""
    # under remat an expert's backward keeps its weights alone, not its output
    expert = jax.checkpoint(_weighed, static_argnums=(5,)) if remat else _weighed

    def add_expert(y, held):
        j, w_gate, w_in, w_out = held
        weight = jnp.sum(jnp.where(top_i == first + j, top_s, 0.0), -1)    # (N, T)
        return y + expert(x, weight, w_gate, w_in, w_out, wrap), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(p["w_in"].shape[0]), p["w_gate"], p["w_in"], p["w_out"]))
    return y


def _moe(p, x, cfg, wrap, remat):
    """This chip's part of the expert layer's output."""
    top_s, top_i = route(p, x, cfg)
    return (routed_part(p, x, cfg["held_first"], top_s, top_i, wrap, remat)
            + shared_part(p, x, wrap))


def _dense(p, x, wrap, remat):
    """The dense MLP, ``ROWS`` rows at a time, computed again in the backward."""
    n, t, d = x.shape
    rows = _row_blocks(t)
    mlp = lambda u: _gated(u, p["gate"]["kernel"], p["in"]["kernel"],  # noqa: E731
                           p["out"]["kernel"], wrap)
    if remat:
        mlp = jax.checkpoint(mlp)
    return lax.map(mlp, x.reshape(n * t // rows, rows, d)).reshape(n, t, d)


def _block(p, x, kind, cfg, wrap, remat):
    eps = cfg["rms_norm_eps"]
    u = _norm(x, p["ln1"], eps)
    x = x + (_kda(p["kda"], u, cfg, wrap, remat) if kind == "kda"
             else _mla(p["attn"], u, cfg, wrap, remat))
    u = _norm(x, p["ln2"], eps)
    return x + (_dense(p["mlp"], u, wrap, remat) if "mlp" in p
                else _moe(p["moe"], u, cfg, wrap, remat))


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


def hidden(params, inputs, cfg, wrap=_plain, remat=True):
    """(N, L) tokens -> (N, L, d) float32 rows behind the final norm."""
    x = params["embed"]["embedding"][inputs]
    block = (jax.checkpoint(_block_static, static_argnums=(2, 3, 4, 5))
             if remat else _block_static)
    for i, kind in enumerate(layer_types(cfg)):
        x = block(params[f"block{i}"], x, kind, _Static(cfg), wrap, remat)
    return _norm(x, params["ln_f"], cfg["rms_norm_eps"])


def logits(params, inputs, cfg, wrap=_plain, remat=True):
    """(N, L) tokens -> (N, L, vocab) float32 logits."""
    return _mm(hidden(params, inputs, cfg, wrap, remat), params["lm_head"]["kernel"], wrap)


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over the vocabulary slice, the head and
    its log-softmax ``ROWS`` rows at a time."""
    x = hidden(params, inputs, cfg, wrap, remat)
    n, t, d = x.shape
    rows = _row_blocks(t)

    def block_sum(xy):
        u, y = xy
        logp = jax.nn.log_softmax(_mm(u, params["lm_head"]["kernel"], wrap), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), -1))

    if remat:
        block_sum = jax.checkpoint(block_sum)
    sums = lax.map(block_sum, (x.reshape(n * t // rows, rows, d),
                               labels.reshape(n * t // rows, rows)))
    return jnp.sum(sums) / (n * t)
