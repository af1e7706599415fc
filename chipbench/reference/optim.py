"""The optimizers of the configurations, in plain ``jax.numpy`` float32.

Written from their published update rules, not from optax: the reference
takes nothing of the program.  ``first_gradient`` inverts the first step's
moment back to the gradient the optimizer was given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(opt: dict, params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    if opt["name"] == "sgd":
        return {"trace": zeros}
    if opt["name"] == "adamw":
        return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}
    raise ValueError(f"no reference optimizer {opt['name']!r}")


def update(opt: dict, params, grads, state):
    lr = opt["lr"]
    if opt["name"] == "sgd":
        # v <- g + m v ; p <- p - lr v  (Sutskever momentum, as torch.optim.SGD)
        trace = jax.tree.map(lambda g, v: g + opt["momentum"] * v, grads, state["trace"])
        new = jax.tree.map(lambda p, v: p - lr * v, params, trace)
        return new, {"trace": trace}
    # AdamW (Loshchilov & Hutter): bias-corrected moments, decoupled decay
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    count = state["count"] + 1
    mu = jax.tree.map(lambda g, m: b1 * m + (1 - b1) * g, grads, state["mu"])
    nu = jax.tree.map(lambda g, v: b2 * v + (1 - b2) * g * g, grads, state["nu"])
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def first_moment_scale(opt: dict) -> float:
    """Factor that turns the first moment after ONE step into the gradient."""
    return 1.0 if opt["name"] == "sgd" else 1.0 / (1.0 - opt["b1"])
