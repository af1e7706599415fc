"""The flash kernels' share of their roofline under the chosen-keys rule: for
every traced ``tpuframe_flash_fwd_select`` / ``_bwd_select`` call the least time
the chip could take (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, from ``kernel_costs`` of the configuration's flops file, which
counts the chosen pairs, ``sum over t of min(t + 1, topk)`` a head, and not the
causal tiles a masked sweep visits) over the time the calls took:
``blockdiff.flash_roofline``'s reading of those calls alone.  The MXU bounds
both kernels.  Moves ``samples_per_s_chip``.  A program without such kernels,
or a configuration without a cost for them, reads as nothing."""

from chipbench import correct


def read(ctx):
    select_calls = correct.load_by_name("layer_metrics", "dsa.flash_ms").select_calls
    return correct.load_by_name("layer_metrics", "blockdiff.flash_roofline").read(
        select_calls(ctx))
