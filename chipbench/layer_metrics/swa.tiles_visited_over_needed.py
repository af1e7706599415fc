"""Tiles the attention sweeps visit under the sliding-window rule over the
tiles' worth of scores that count: ``blockdiff.tiles_visited_over_needed``'s
reading of the program's counters ``attention/tiles_visited`` /
``attention/tiles_needed`` (static counts a step, both in tiles of the
backward kernel's side, forward and backward together; the full-attention
layer adds to neither), under a name of this cell's (that metric's list of
cells is another's); 1.0 is no waste; moves ``samples_per_s_chip``.  A program
without the counters reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "blockdiff.tiles_visited_over_needed").read(ctx)
