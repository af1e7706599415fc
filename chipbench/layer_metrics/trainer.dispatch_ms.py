"""``train/step`` span (the dispatch of one step) per step; moves
``samples_per_s_chip``."""


def read(ctx):
    total, count = ctx["spans"]["span/train/step"]
    return 1e3 * total / count if count else None
