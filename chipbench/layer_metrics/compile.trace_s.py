"""Seconds of the set-up in which jax traced Python into jaxprs: the sum of
the ``compile/jax_trace`` records, every thread's, each less what is nested in
it; moves ``setup_s``.  A program without the records reads as nothing."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.phase_seconds(ctx, "compile/jax_trace")
