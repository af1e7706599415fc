"""Seconds of precompile that bought nothing: the sum of the
``compile/precompile_step`` spans whose ``used`` is false (the step's first
real batch did not dispatch the executable the span kept: the signature never
matched, or no template could be built); 0 where the first step is the join
of the precompile; moves ``setup_s``."""

from chipbench.layer_metrics import setup_window


def read(ctx):
    return setup_window.total(ctx, "compile/precompile_step",
                              keep=lambda r: not r.attrs.get("used"))
