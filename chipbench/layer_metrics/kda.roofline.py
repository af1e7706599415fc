"""The vector-decay delta rule's kernels' share of their roofline: for every
traced ``tpuframe_kda_*`` call the least time the chip could take (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, from
``kernel_costs`` of the configuration's flops file, which counts the
recurrence's own operations and the least bytes any schedule moves, not what
the chunked schedule computes) over the time the calls took.  Moves
``samples_per_s_chip``.  A program without such kernels, or a configuration
without a cost for them, reads as nothing."""

from chipbench import correct


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not peaks:
        return None
    costs = getattr(correct.load_by_name("flops", ctx["cfg"]["name"]), "kernel_costs",
                    lambda *_: {})(ctx["cfg"], ctx["global_batch"] // ctx["chips"])
    least = took = 0.0
    for name, k in t["kernels"].items():
        if name.startswith("tpuframe_kda_") and name in costs:
            c = costs[name]
            least += k["calls"] * max(c["flops"] / peaks["bf16_flops_per_s"],
                                      c["bytes"] / peaks["hbm_bytes_per_s"])
            took += k["seconds"]
    return 100.0 * least / took if took else None
