"""Plain reference for ``sdar-30b-a3b-chat``: one chip's share of the model
and its block-diffusion training objective, float32 ``jax.numpy``, nothing
of the program imported.

Written from the source's ``config.json`` (``model_type`` ``sdar_moe``, a
Qwen3-MoE-shaped decoder) and the block-diffusion training recipe (BD3-LM,
arXiv:2503.09573, section 3 and its vectorised training of appendix B; SDAR
adapts an autoregressive checkpoint with the same row and mask):

* a layer, pre-norm: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
  every layer is sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``); a
  final RMSNorm and an untied head;
* Attn: ``q = W_q n`` (32 heads of 128), ``k = W_k n``, ``v = W_v n`` (4 heads
  of 128); every query head and every key head RMS-normalised over its 128
  dimensions with a learned scale; rotary over all 128 dimensions (rotate-half,
  theta 1e6, no scaling) at the row's position ids; query head ``j`` uses
  key/value head ``j // 8``; scores ``q.k / sqrt(128)`` under the mask ``M``,
  softmax, ``W_o`` of the concatenated heads;
* MoE: ``g = softmax(W_r n)`` over all 128 router outputs, the 8 largest, their
  gates divided by their sum; ``y = sum over the chosen experts e held here of
  g_e W_down_e (silu(W_gate_e n) * W_up_e n)``.  This chip holds ``num_experts``
  of the router's ``num_experts_published`` (experts ``held_first ..``); what
  the others would add is left out, as in the program.  Every held expert is
  computed on every position and masked by membership in its top 8: no sort,
  no grouped product.  No shared expert.  Balance loss, the Switch form over
  all 128 outputs and all positions of the batch: ``coef * 128 * sum_e
  (share of positions whose first choice is e) * (mean gate of e)``;
* the training row: a sample is ``L`` clean tokens ``x0`` in blocks of ``B``;
  block ``b`` has a noise level ``t_b = eps + (1 - eps) u_b``, position ``i``
  of it is masked iff its own uniform draw ``u_i < t_b``, ``xt_i`` is the mask
  token where masked and ``x0_i`` elsewhere.  The model runs once on ``z = [xt
  ; x0]``, ``2 L`` positions at position ids ``[0..L-1, 0..L-1]``.  With
  ``noised(i) = i < L`` and ``blk(i) = (i mod L) // B``, query ``i`` sees key
  ``j`` iff both are noised and ``blk(i) == blk(j)``, or ``i`` is noised, ``j``
  clean and ``blk(j) < blk(i)``, or both are clean and ``blk(j) <= blk(i)``;
* the objective: logits of the noised half only, position ``i`` predicting
  ``x0_i`` (no shift); ``loss = (1/L) sum_b (1/t_b) sum_{i in b, masked}
  CE(l_i, x0_i)``, the mean over rows, plus the balance loss.

The draws travel in the sample: ``inputs`` is (N, L, 3) int32, column 0 the
clean token, column 1 the position's mask draw, column 2 the level draw (read
at each block's first position); a draw ``d`` stands for ``(d + 0.5) /
mask_token_id`` (draws and data tokens share the range below the mask token).
``labels`` is ignored.

Departures, each under ``assumed`` in the configuration's file: block length,
the linear schedule and its ``eps``, the ``1/t`` weight, the head norms' form,
no shift, the mask token (the slice's last row: the source's id lies outside
the slice), ``router_aux_loss_coef``.

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
        "k": cfg["num_experts_per_tok"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
    }


def param_shapes(cfg) -> dict:
    z, std = _sizes(cfg), 0.02
    mat = lambda *shape: (tuple(shape), ("normal", std))  # noqa: E731
    norm = lambda n: {"scale": ((n,), "ones")}  # noqa: E731
    # the seeded weights' scales that differ by layer come as one value a layer
    head_norm = lambda i: {"scale": ((z["hd"],), ("const", cfg["head_norm_init"][i]))}  # noqa: E731
    d, hd = z["d"], z["hd"]
    tree = {"embed": {"embedding": ((z["vocab"], d), ("normal", cfg["embedding_init_std"]))}}
    for i in range(z["layers"]):
        tree[f"block{i}"] = {
            "ln1": norm(d),
            "attn": {"query": {"kernel": mat(d, z["heads"] * hd)},
                     "key": {"kernel": mat(d, z["kv"] * hd)},
                     "value": {"kernel": mat(d, z["kv"] * hd)},
                     "q_norm": head_norm(i), "k_norm": head_norm(i),
                     "attn_out": {"kernel": ((z["heads"] * hd, d),
                                             ("normal", cfg["attn_out_init_std"][i]))}},
            "ln2": norm(d),
            "moe": {"router": {"kernel": mat(d, z["router"])},
                    "w_gate": mat(z["held"], d, z["expert"]),
                    "w_in": mat(z["held"], d, z["expert"]),
                    "w_out": mat(z["held"], z["expert"], d)},
        }
    tree["ln_f"] = norm(d)
    tree["lm_head"] = {"kernel": mat(d, z["vocab"])}
    return tree


# -- the forward process and the mask ---------------------------------------
def forward_process(inputs, cfg):
    """(x0, masked, t) of (N, L, 3) samples, each (N, L): the clean tokens,
    which positions of the noised copy show the mask token, and each
    position's block's noise level."""
    b, eps, rng = cfg["block_length"], cfg["noise_eps"], cfg["mask_token_id"]
    uniform = lambda d: (d.astype(jnp.float32) + 0.5) / rng  # noqa: E731
    t = eps + (1.0 - eps) * uniform(inputs[:, ::b, 2])
    t = jnp.repeat(t, b, axis=1)
    return inputs[..., 0], uniform(inputs[..., 1]) < t, t


def dense_mask(length: int, block: int) -> np.ndarray:
    """(2 L, 2 L) bool, [query, key], from the rule's three cases."""
    pos = np.arange(2 * length)
    noised, blk = pos < length, (pos % length) // block
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


# -- layers -------------------------------------------------------------------
def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """Rotate-half rotary over the whole width of (N, T, H, width), at
    position ids 0 .. T/2 - 1 twice in a row."""
    t, dim = x.shape[1], x.shape[-1]
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.tile(np.arange(t // 2, dtype=np.float64), 2)[:, None] * f
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _head(q, k, v, block, wrap):
    """One head under the block-diffusion mask: (N, T, hd) each."""
    seen = jnp.asarray(dense_mask(q.shape[1] // 2, block))
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
    q = _rope(_rms(q, p["q_norm"], z["eps"]), cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], z["eps"]), cfg["rope_theta"])
    head = jax.checkpoint(_head, static_argnums=(3, 4)) if remat else _head

    def group(qkv):
        """The h / kv query heads that share one key/value head."""
        qg, kg, vg = qkv                         # (N, T, h/kv, hd), (N, T, hd) x 2
        return jnp.stack([head(qg[:, :, j], kg, vg, cfg["block_length"], wrap)
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
    """(N, L, 3) samples -> ((N, L, vocab) float32 logits of the noised half,
    the balance loss)."""
    x0, masked, _ = forward_process(inputs, cfg)
    row = jnp.concatenate([jnp.where(masked, cfg["mask_token_id"], x0), x0], axis=1)
    x = params["embed"]["embedding"][row]
    block = jax.checkpoint(_block_static, static_argnums=(2, 3, 4)) if remat else _block_static
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, a = block(params[f"block{i}"], x, _Static(cfg), wrap, remat)
        aux = aux + a
    x = _rms(x[:, :x0.shape[1]], params["ln_f"], cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"], wrap), aux


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """The mean over rows of ``(1/L) sum over masked i of CE(l_i, x0_i) /
    t_blk(i)``; the gradient is that of it + the balance loss (see the
    module's docstring).  ``labels`` is ignored."""
    x0, masked, t = forward_process(inputs, cfg)
    lg, aux = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(logp, x0[..., None], -1)[..., 0]
    data = jnp.mean(jnp.where(masked, ce / t, 0.0))
    return data + (aux - lax.stop_gradient(aux))
