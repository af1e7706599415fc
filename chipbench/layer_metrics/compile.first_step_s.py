"""``fit()`` entry to the first step dispatched: precompile join, or the
lazy trace and compile where the precompile kept no executable; moves
``setup_s``."""


def read(ctx):
    return ctx["compile_first_step_s"]
