"""Seconds of the set-up in which the persistent cache answered a compile
request: the sum of the ``compile/jax_backend`` records with ``cache`` =
``hit`` (the key's hashing, the read, deserialize and load); moves
``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.phase_seconds(ctx, "compile/jax_backend", ("hit",))
