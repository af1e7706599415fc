"""Host clock around ``core.initialize()``; moves ``setup_s``."""


def read(ctx):
    return ctx["runtime_init_s"]
