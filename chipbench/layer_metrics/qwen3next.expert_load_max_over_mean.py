"""Fullest held expert over the mean held expert in the Qwen3-Next cell (16 of
512 experts held, 10 choices a token, softmax gates renormalised), worst
layer, mean over the steps of the last metrics window:
``moe.expert_load_max_over_mean``'s reading of the program's gauge, under a
name of this cell's (that metric's list of cells is another's); moves
``samples_per_s_chip``.  A program without the gauge reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.expert_load_max_over_mean").read(ctx)
