"""What the loop thread did from ``fit()`` entry to the end of the first
``train/step`` that was neither a wait for the precompile (``compile/wait``)
nor a trace, a lowering or a load of its own (the ``compile/jax_*`` records
of that thread in that time): Python, the loader's first batch, callbacks;
moves ``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    setup = setup_window.read(ctx)
    if setup is None:
        return None
    start, end = setup["fit_start"].start_ns, setup["first_step"].end_ns
    inside = [r for r in setup["records"] if r.thread == setup["fit_start"].thread
              and r.start_ns >= start and r.end_ns <= end]
    named = sum(setup_window.seconds(r) for r in inside if r.name == "compile/wait")
    named += sum(setup_window.own_seconds(r) for r in inside
                 if r.name in setup_window.PHASES)
    return (end - start) / 1e9 - named
