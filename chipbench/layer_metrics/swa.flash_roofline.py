"""The sliding-window layers' flash kernels' share of their roofline: for
every traced ``tpuframe_flash_fwd_window`` / ``_bwd_window`` call the least time
the chip could take (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, from ``kernel_costs`` of the configuration's flops file, which
counts the band's own area and not the tiles visited) over the time the calls
took: ``blockdiff.flash_roofline``'s reading of those calls alone.  The MXU
bounds both kernels.  Moves ``samples_per_s_chip``.  A program without such
kernels, or a configuration without a cost for them, reads as nothing."""

from chipbench import correct


def read(ctx):
    window_calls = correct.load_by_name("layer_metrics", "swa.flash_ms").window_calls
    return correct.load_by_name("layer_metrics", "blockdiff.flash_roofline").read(
        window_calls(ctx))
