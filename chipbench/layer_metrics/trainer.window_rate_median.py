"""Median over the span's windows (the first after warm-up left out) of
global batch x ``log_interval`` steps / window time / chips: the program's
pace with any stall left out.  The end-to-end rate counts all samples over
all time, so the two apart say that something stalled inside the span;
moves ``samples_per_s_chip``."""


def read(ctx):
    return ctx["rate_window_median"]
