"""Share of the measured span the training loop spent in ``train/data_wait``;
moves ``samples_per_s_chip``."""


def read(ctx):
    return 100.0 * ctx["spans"]["span/train/data_wait"][0] / ctx["span_s"]
