"""Device time of the gated delta rule's kernels (the ``tpuframe_gated_delta_*``
Pallas custom calls: the pass over the chunks, one forward and one backward a
``linear_attention`` layer, 3 + 3 a step in ``qwen3next_seq8192``) per step,
from the trace; moves ``samples_per_s_chip``.  What the schedule computes
inside a chunk stays XLA's and is not in it.  A program without such kernels
reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_gated_delta")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
