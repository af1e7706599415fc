"""Calls of the head-norm-and-rotary kernels (``tpuframe_head_norm_rope*``) per
step, from the trace: four for every attention layer that took them (query and
key, forward and backward); moves ``samples_per_s_chip``.  A program without
such kernels reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_head_norm_rope")]
    return sum(k["calls"] for k in calls) / t["steps"] if calls else None
