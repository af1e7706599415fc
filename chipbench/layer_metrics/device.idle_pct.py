"""1 - device busy time / traced window (trace); moves ``samples_per_s_chip``."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
