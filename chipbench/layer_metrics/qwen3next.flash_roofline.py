"""The flash-attention kernels' share of their roofline in the Qwen3-Next
cell, each call priced by ``kernel_costs`` of the configuration's flops file
(the causal triangle at 256-wide heads): ``blockdiff.flash_roofline``'s
reading, under a name of this cell's (that metric's list of cells is
another's); the MXU bounds both calls; moves ``samples_per_s_chip``.  A program
without such kernels, or a configuration without a cost for them, reads as
nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "blockdiff.flash_roofline").read(ctx)
