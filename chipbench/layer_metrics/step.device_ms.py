"""Device busy time per step from the trace (union of operation intervals,
mean over the chips); moves ``samples_per_s_chip``."""


def read(ctx):
    t = ctx["trace"]
    return 1e3 * t["busy_s"] / t["steps"] if t and t["steps"] else None
