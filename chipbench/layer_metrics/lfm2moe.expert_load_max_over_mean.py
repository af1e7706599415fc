"""Fullest held expert over the mean held expert in the LFM2 cell (8 of 32
experts held, 4 choices a token under a sigmoid and a selection bias), worst
layer, mean over the steps of the last metrics window:
``moe.expert_load_max_over_mean``'s reading of the program's gauge, under a
name of this cell's (that metric's list of cells is another's); moves
``samples_per_s_chip``.  A program without the gauge reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.expert_load_max_over_mean").read(ctx)
