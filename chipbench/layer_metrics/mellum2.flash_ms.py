"""Device time of all flash-attention kernels of the Mellum2 cell per step (the
``tpuframe_flash*`` Pallas custom calls: one forward and one backward a layer,
the three window layers' under the band rule's names and the full layer's
under the plain ones, 8 a step), from the trace: ``attention.flash_ms``'s
reading, under a name of this cell's (that metric's list of cells is
another's); moves ``samples_per_s_chip``.  A program without such kernels
reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "attention.flash_ms").read(ctx)
