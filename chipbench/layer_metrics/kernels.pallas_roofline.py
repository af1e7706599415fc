"""The Pallas kernels' share of their roofline: for every traced call the
least time the chip could take (the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from ``kernel_costs`` of the configuration's
flops file) over the time the calls took.  Bandwidth bounds every kernel
here.  Kernels without a cost function are left out of both sums; moves
``samples_per_s_chip``."""

from chipbench import correct


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not t["kernels"] or not peaks:
        return None
    costs = correct.load_by_name("flops", ctx["cfg"]["name"]).kernel_costs(
        ctx["cfg"], ctx["global_batch"] // ctx["chips"])
    least = took = 0.0
    for name, k in t["kernels"].items():
        if name in costs:
            c = costs[name]
            least += k["calls"] * max(c["flops"] / peaks["bf16_flops_per_s"],
                                      c["bytes"] / peaks["hbm_bytes_per_s"])
            took += k["seconds"]
    return 100.0 * least / took if took else None
