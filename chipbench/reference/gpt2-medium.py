"""Plain reference of ``gpt2-medium``: GPT-2 forward and next-token loss.

Radford et al. 2019 with the sizes of ``openai-community/gpt2-medium``
``config.json``: pre-LayerNorm blocks, learned positions, causal softmax
attention with 1/sqrt(head) scaling, a 4x GELU (tanh form, ``gelu_new``)
MLP, a final LayerNorm.  Float32 ``jax.numpy`` at ``highest`` matmul
precision; no kernels, no flax, nothing imported from the program.  The
parameter tree uses the program's checkpoint names so the harness can hand
the same seeded weights to both.

Departures from the source, as the configuration states: the head is not
tied to the embedding, the attention projections carry no bias, no
dropout, LayerNorm epsilon as the configuration file gives it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def param_shapes(cfg) -> dict:
    d, v, std = cfg["n_embd"], cfg["vocab_size"], 0.02
    mat = lambda a, b: ((a, b), ("normal", std))  # noqa: E731
    ln = lambda: {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}  # noqa: E731
    tree = {"embed": {"embedding": mat(v, d)},
            "pos_embed": {"embedding": mat(cfg["n_positions"], d)}}
    for i in range(cfg["n_layer"]):
        tree[f"block{i}"] = {
            "ln1": ln(),
            "attn": {"query": {"kernel": mat(d, d)}, "key": {"kernel": mat(d, d)},
                     "value": {"kernel": mat(d, d)}, "attn_out": {"kernel": mat(d, d)}},
            "ln2": ln(),
            "mlp_in": {"kernel": mat(d, 4 * d), "bias": ((4 * d,), "zeros")},
            "mlp_out": {"kernel": mat(4 * d, d), "bias": ((d,), "zeros")},
        }
    tree["ln_f"] = ln()
    tree["lm_head"] = {"kernel": mat(d, v)}
    return tree


def _ln(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _plain(f):
    return f


def _mm(x, w, wrap):
    return wrap(lambda a, b: jnp.dot(a, b, precision=_HI))(x, w)


def _block(p, x, heads, eps, wrap):
    b, t, d = x.shape
    h = _ln(x, p["ln1"], eps)
    split = lambda a: a.reshape(b, t, heads, d // heads)  # noqa: E731
    q = split(_mm(h, p["attn"]["query"]["kernel"], wrap))
    k = split(_mm(h, p["attn"]["key"]["kernel"], wrap))
    v = split(_mm(h, p["attn"]["value"]["kernel"], wrap))
    scores = wrap(lambda a, b: jnp.einsum("bqhd,bkhd->bhqk", a, b, precision=_HI))(q, k)
    scores = scores / jnp.sqrt(jnp.float32(d // heads))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = wrap(lambda a, b: jnp.einsum("bhqk,bkhd->bqhd", a, b, precision=_HI))(probs, v)
    x = x + _mm(a.reshape(b, t, d), p["attn"]["attn_out"]["kernel"], wrap)
    h = _ln(x, p["ln2"], eps)
    h = jax.nn.gelu(_mm(h, p["mlp_in"]["kernel"], wrap) + p["mlp_in"]["bias"],
                    approximate=True)
    return x + _mm(h, p["mlp_out"]["kernel"], wrap) + p["mlp_out"]["bias"]


def logits(params, tokens, cfg, wrap=_plain, remat=True):
    """(N, T) int tokens -> (N, T, vocab) float32.  ``wrap`` decorates every
    matmul (the control rounds their operands)."""
    t = tokens.shape[1]
    x = params["embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][:t][None]
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4)) if remat else _block
    for i in range(cfg["n_layer"]):
        x = block(params[f"block{i}"], x, cfg["n_head"], cfg["reference"]["layer_norm_epsilon"], wrap)
    x = _ln(x, params["ln_f"], cfg["reference"]["layer_norm_epsilon"])
    return _mm(x, params["lm_head"]["kernel"], wrap)


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean next-token cross entropy over every position of every row."""
    lg = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1))
