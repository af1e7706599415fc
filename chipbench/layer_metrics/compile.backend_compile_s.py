"""Seconds of the set-up in which the backend really compiled: the sum of
the ``compile/jax_backend`` records with ``cache`` = ``miss`` or
``uncached``; 0 in a warm run, a checkout's first run shows it; moves
``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.phase_seconds(ctx, "compile/jax_backend", ("miss", "uncached"))
