"""``compile/backend_compiles`` counted inside the measured span; must be 0
(a run with more counts every step as failed); moves ``setup_s``."""


def read(ctx):
    return ctx["counters"]["compile/backend_compiles"]
