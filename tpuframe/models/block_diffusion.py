"""Block-diffusion language model: a decoder trained to denoise blocks.

A sample is ``L`` clean tokens in blocks of ``block_length``.  Training
(BD3-LM, arXiv:2503.09573; SDAR's recipe for adapting an autoregressive
checkpoint) runs the decoder ONCE on a row of ``2 L`` positions, the
noised copy ``xt`` then the clean copy ``x0``, both at position ids
``0 .. L - 1``, under `ops.ring_attention.BlockDiffusionMask`: a noised
position sees its whole block and the clean copy of the blocks before
it, a clean position the clean blocks up to its own.  The logits of the
noised half predict the clean token at the same position (no shift), and
the objective weighs the masked positions by one over their block's
noise level.

The forward process's randomness travels in the sample, as a collator's
would: the model's input is (B, L, 3) int32, column 0 the clean token,
column 1 the position's mask draw, column 2 the noise-level draw (read
at each block's first position), a draw ``d`` standing for the uniform
``(d + 0.5) / mask_token`` (the mask token is the vocabulary's last row,
and draws and data tokens share the range below it).  So a step is a
pure function of its batch, and a plain reference can follow it from
the same arrays.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpuframe.models.transformer import TransformerLM
from tpuframe.ops.ring_attention import BlockDiffusionMask

__all__ = ["BlockDiffusionLM", "forward_process", "block_diffusion_losses"]


def forward_process(inputs: jax.Array, *, block: int, draw_range: int, eps: float):
    """(clean tokens, masked, level) from a (B, L, 3) sample, each (B, L).

    One noise level a block, linear schedule: ``t = eps + (1 - eps) u``
    with ``u`` the block's level draw; a position is masked iff its own
    uniform draw lies under its block's level."""
    as_uniform = lambda d: (d.astype(jnp.float32) + 0.5) / draw_range  # noqa: E731
    level = eps + (1.0 - eps) * as_uniform(inputs[:, ::block, 2])
    level = jnp.repeat(level, block, axis=1)
    return inputs[..., 0], as_uniform(inputs[..., 1]) < level, level


def block_diffusion_losses(logits: jax.Array, inputs: jax.Array, *, block: int,
                           draw_range: int, eps: float) -> jax.Array:
    """(B,) the objective a row: ``(1/L) sum over masked i of CE(l_i, x0_i) / t_blk(i)``."""
    x0, masked, level = forward_process(
        inputs, block=block, draw_range=draw_range, eps=eps)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, x0)
    return jnp.mean(jnp.where(masked, ce / level, 0.0), axis=-1)


class BlockDiffusionLM(TransformerLM):
    """`TransformerLM`'s layers on block-diffusion training rows:
    (B, L, 3) int32 samples (module docstring) -> (B, L, vocab) logits of
    the noised half.  It brings its own objective (`objective`), which
    `Trainer` trains where no ``loss_fn`` is given."""

    block_length: int = 4
    #: the lowest noise level
    noise_eps: float = 1e-3

    @property
    def mask_token(self) -> int:
        """The token a masked position shows: the vocabulary's last row.
        Data tokens and the sample's draws share the range below it."""
        return self.vocab_size - 1

    def _process(self) -> dict:
        return {"block": self.block_length, "eps": self.noise_eps,
                "draw_range": self.mask_token}

    @nn.compact
    def __call__(self, inputs: jax.Array, train: bool = False) -> jax.Array:
        length = inputs.shape[1]
        with jax.named_scope("tpuframe/blockdiff/noise"):
            x0, masked, _ = forward_process(inputs, **self._process())
            xt = jnp.where(masked, self.mask_token, x0)
            row = jnp.concatenate([xt, x0], axis=1)
        return self._decode(
            row, train, positions=np.tile(np.arange(length), 2),
            mask=BlockDiffusionMask(length, self.block_length), head_len=length)

    def objective(self, logits: jax.Array, batch) -> jax.Array:
        """(B,) losses from the model's output and the batch it came from."""
        with jax.named_scope("tpuframe/blockdiff/loss"):
            return block_diffusion_losses(logits, batch["input"], **self._process())
