"""Device time of the flash-attention kernels of the Qwen3-Next cell per step
(the ``tpuframe_flash_fwd`` / ``_bwd`` Pallas custom calls of its one
full-attention layer: plain causal, 16 heads of 256 over 2 key/value heads,
8192 keys; 1 + 1 a step), from the trace: ``attention.flash_ms``'s reading,
under a name of this cell's (that metric's list of cells is another's); moves
``samples_per_s_chip``.  A program without such kernels reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "attention.flash_ms").read(ctx)
