"""MoE top-k dispatch/combine: capacity-truncated scatter, not one-hots.

The GShard dense-dispatch formulation (``models/moe.py``'s original
path, kept here as the reference oracle) materializes a ``(kN, E, C)``
one-hot dispatch tensor and einsums tokens through it twice — at
N=4096 tokens, E=8 experts, k=2 that is a ~84M-element tensor built,
read and re-read per layer purely to move rows around.  The fused path
does the same routing with a scatter-add into the ``(E, C, D)`` expert
buffers and a gather back out: no ``(kN, E, C)`` tensor ever exists,
the data movement is O(kN·D) instead of O(kN·E·C), and XLA lowers the
``at[].add``/gather pair to dynamic-update-slice loops the TPU runs off
the VPU.  Bit-close, not bit-identical: the scatter accumulates token
contributions in a different order than the einsum's reduction, so
results agree to float tolerance (atol 1e-5 f32 — pinned by the parity
test).

Routing semantics are shared (one ``_routing`` implementation): top-k
choices fill expert buffers in choice-major order, a token's slot past
``capacity`` is dropped (combine weight zero), exactly the Switch
behavior the reference implements.

``moe_dispatch_combine`` runs the fused path (it is pure XLA, so it
engages on every backend); ``fused=False`` is the dense reference, for
tests.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["moe_dispatch_combine", "moe_dispatch_combine_reference"]


def _routing(gate_idx: jax.Array, e: int, capacity: int):
    """Shared Switch-style routing: flattened choice-major assignment.

    Returns ``(choice_exp, pos, keep, tok_idx)`` over the ``(k*N,)``
    flattened frame — expert of each slot, its position inside that
    expert's buffer (running count, so choice 0 fills before choice 1),
    whether it fits under ``capacity``, and the token it came from.
    """
    n, k = gate_idx.shape
    choice_exp = gate_idx.T.reshape(-1)  # (kN,) choice-major
    onehot = jax.nn.one_hot(choice_exp, e, dtype=jnp.int32)  # (kN, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - onehot
    pos = jnp.sum(pos_in_expert, axis=-1)  # (kN,)
    keep = pos < capacity
    tok_idx = jnp.tile(jnp.arange(n), k)
    return choice_exp, pos, keep, tok_idx


def moe_dispatch_combine_reference(
    tokens: jax.Array,
    gate_vals: jax.Array,
    gate_idx: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    capacity: int,
    act: Callable = jax.nn.gelu,
) -> jax.Array:
    """jnp oracle: the GShard dense one-hot dispatch/combine einsums."""
    n, d = tokens.shape
    e = w_in.shape[0]
    choice_exp, pos, keep, tok_idx = _routing(gate_idx, e, capacity)
    dtype = w_in.dtype
    disp = (
        jax.nn.one_hot(choice_exp, e, dtype=tokens.dtype)[:, :, None]
        * jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                         dtype=tokens.dtype)[:, None, :]
        * keep[:, None, None]
    )  # (kN, E, C)
    gates_flat = gate_vals.T.reshape(-1)  # choice-major to match
    expert_in = jnp.einsum("fec,fd->ecd", disp, tokens[tok_idx].astype(dtype))
    h = act(jnp.einsum("ecd,edh->ech", expert_in, w_in))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_out)
    combine = disp * gates_flat[:, None, None]  # (kN, E, C)
    out_flat = jnp.einsum("fec,ecd->fd", combine, expert_out)
    return jnp.zeros((n, d), out_flat.dtype).at[tok_idx].add(out_flat)


def moe_dispatch_combine(
    tokens: jax.Array,
    gate_vals: jax.Array,
    gate_idx: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    capacity: int,
    act: Callable = jax.nn.gelu,
    fused: bool = True,
) -> jax.Array:
    """Top-k expert MLP: tokens -> gated mixture of expert outputs.

    Args:
      tokens: (N, D) flattened tokens.
      gate_vals: (N, k) renormalized gate weights of the chosen experts.
      gate_idx: (N, k) chosen expert ids.
      w_in / w_out: (E, D, H) / (E, H, D) expert-stacked MLP weights.
      capacity: per-expert buffer slots; overflow slots are dropped.
      fused: False runs :func:`moe_dispatch_combine_reference` (tests).

    Returns (N, D) combined outputs (dropped tokens contribute zero).
    Differentiable end to end — the scatter/gather pair transposes
    natively, no custom VJP needed.
    """
    n, d = tokens.shape
    e = w_in.shape[0]
    if gate_vals.shape != gate_idx.shape or gate_idx.shape[0] != n:
        raise ValueError(
            f"gate_vals/gate_idx must be (N, k), got {gate_vals.shape}/"
            f"{gate_idx.shape} for N={n}"
        )
    if not fused:
        return moe_dispatch_combine_reference(
            tokens, gate_vals, gate_idx, w_in, w_out,
            capacity=capacity, act=act,
        )
    choice_exp, pos, keep, tok_idx = _routing(gate_idx, e, capacity)
    dtype = w_in.dtype
    pos_c = jnp.clip(pos, 0, capacity - 1)
    # dispatch: scatter kept token rows straight into the expert buffers
    # (dropped slots are zeroed first, so their clipped position cannot
    # pollute a real slot)
    x = tokens[tok_idx].astype(dtype) * keep[:, None].astype(dtype)
    expert_in = jnp.zeros((e, capacity, d), dtype).at[choice_exp, pos_c].add(x)
    h = act(jnp.einsum("ecd,edh->ech", expert_in, w_in))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_out)
    # combine: gather each slot's output back and weight by its gate
    gates_flat = gate_vals.T.reshape(-1)  # choice-major to match
    weight = (gates_flat * keep).astype(expert_out.dtype)
    out_flat = expert_out[choice_exp, pos_c] * weight[:, None]
    return jnp.zeros((n, d), out_flat.dtype).at[tok_idx].add(out_flat)
