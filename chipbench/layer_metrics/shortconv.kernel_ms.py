"""Device time of the short-convolution kernels (the ``tpuframe_short_conv_*``
Pallas custom calls: one forward and one backward a ``conv`` layer) per step,
from the trace; moves ``samples_per_s_chip``.  A program without such kernels
reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_short_conv")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
