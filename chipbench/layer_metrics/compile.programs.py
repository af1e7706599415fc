"""Compile requests of the set-up: its ``compile/jax_backend`` records, one
a program that was loaded from the cache or compiled; moves ``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.total(ctx, "compile/jax_backend", of=lambda r: 1)
