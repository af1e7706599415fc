"""Calls of the vector-decay delta rule's chunk-local kernels
(``tpuframe_kdachunk_*``) per step, from the trace: three for every ``kda``
layer that took them (forward, again in the backward pass, transposed), 12 in
``kimilinear_seq4096``; moves ``samples_per_s_chip``.  A program without such
kernels reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_kdachunk_")]
    return sum(k["calls"] for k in calls) / t["steps"] if calls else None
