"""Seconds in the Trainer's state initialiser (``setup/state_init``: the
jitted ``init_fn``'s trace, lowering, load or compile, and its dispatch);
moves ``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.total(ctx, "setup/state_init")
