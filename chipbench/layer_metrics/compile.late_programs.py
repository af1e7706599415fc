"""Programs first met after the first step: the set-up's
``compile/jax_backend`` records that closed after the first ``train/step``
did, in the first steps or the warm-up, each a stall there; moves
``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    setup = setup_window.read(ctx)
    if setup is None:
        return None
    after = setup["first_step"].end_ns
    return sum(1 for r in setup["records"]
               if r.name == "compile/jax_backend" and r.end_ns > after)
