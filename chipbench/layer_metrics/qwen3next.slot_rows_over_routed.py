"""Rows the expert layer's slot buffers carried for each (token, choice) pair
routed to an expert held here, in the Qwen3-Next cell (buffers of 5,120 rows
for the ~2,560 of 81,920 pairs routed here): ``moe.slot_rows_over_routed``'s
reading of the program's counters, under a name of this cell's (that metric's
list of cells is another's); 1.0 is no waste; moves ``samples_per_s_chip``.
A program without the counters reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.slot_rows_over_routed").read(ctx)
