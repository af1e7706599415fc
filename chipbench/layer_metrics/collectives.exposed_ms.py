"""Collective operations' device time per step during which no compute ran
on that device (trace); nothing to read on one chip; moves
``samples_per_s_chip``."""


def read(ctx):
    t = ctx["trace"]
    if not t or ctx["chips"] < 2 or not t["steps"]:
        return None
    return 1e3 * t["exposed_collective_s"] / t["steps"]
