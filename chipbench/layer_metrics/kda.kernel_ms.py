"""Device time of the vector-decay delta rule's kernels (the ``tpuframe_kda_*``
Pallas custom calls: the pass over the chunks, one forward and one backward a
``kda`` layer, 4 + 4 a step in ``kimilinear_seq4096``) per step, from the
trace; moves ``samples_per_s_chip``.  What the schedule computes inside a
chunk is XLA's and is not in it.  A program without such kernels reads as
nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_kda_")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
