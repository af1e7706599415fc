"""``data/assemble`` span per batch (loader), measured span; moves
``samples_per_s_chip``."""


def read(ctx):
    total, count = ctx["spans"]["span/data/assemble"]
    return 1e3 * total / count if count else None
