"""Share of the rows the grouped expert product multiplied that were no real
assignment, in the block-diffusion cell (16 groups of ~512 rows of 768-wide
experts): ``moe.padded_rows_pct``'s reading of the program's ``moe/*``
counters, under a name of this cell's (that metric's list of cells is
another's); moves ``samples_per_s_chip``.  A program without the counters
reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "moe.padded_rows_pct").read(ctx)
