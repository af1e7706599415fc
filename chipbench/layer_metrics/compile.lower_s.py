"""Seconds of the set-up in which jax lowered jaxprs to MLIR modules (every
Mosaic kernel at each of its call sites is lowered here): the sum of the
``compile/jax_lower`` records; moves ``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.phase_seconds(ctx, "compile/jax_lower")
