"""``data/h2d`` span per batch (prefetcher thread), measured span; moves
``samples_per_s_chip``."""


def read(ctx):
    total, count = ctx["spans"]["span/data/h2d"]
    return 1e3 * total / count if count else None
