"""Device time of the expert layers' grouped products in the Keye-VL-2.0 cell
per step (the ``tpuframe_grouped*`` Pallas custom calls: 8 held experts of
2048 x 768 with ~512 rows each): ``moe.experts_ms``'s reading, under a name of
this cell's (that metric's list of cells is another's); moves
``samples_per_s_chip``.  A program whose expert products are not kernels of
its own reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.experts_ms").read(ctx)
