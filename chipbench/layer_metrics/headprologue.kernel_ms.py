"""Device time of the head-norm-and-rotary kernels (the ``tpuframe_head_norm_rope*``
Pallas custom calls: one forward and one backward for the query and for the key
projection of every attention layer that norms its heads and turns them) per
step, from the trace; moves ``samples_per_s_chip``.  A program without such
kernels reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_head_norm_rope")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
