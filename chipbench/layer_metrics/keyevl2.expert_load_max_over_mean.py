"""Fullest held expert over the mean held expert in the Keye-VL-2.0 cell (8 of
128 experts held, 8 chosen a token): ``moe.expert_load_max_over_mean``'s
reading of the program's gauge, under a name of this cell's (that metric's
list of cells is another's); moves ``samples_per_s_chip``.  A program without
the gauge reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.expert_load_max_over_mean").read(ctx)
