"""Device time of the ``tpuframe_*`` Pallas custom calls per step, from the
trace; moves ``samples_per_s_chip``."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["kernels"] or not t["steps"]:
        return None
    return 1e3 * sum(k["seconds"] for k in t["kernels"].values()) / t["steps"]
