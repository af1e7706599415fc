"""Device time of the expert layer's grouped products (the ``tpuframe_grouped*``
Pallas custom calls: rows x weights, cotangent x weights transposed, rows
transposed x cotangent, three of each an expert layer) per step, from the
trace; moves ``samples_per_s_chip``.  A program whose expert products are not
kernels of its own (XLA names its ragged-dot kernel after the HLO op) reads as
nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_grouped")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
