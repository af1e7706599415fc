"""Share of the measured span spent in ``train/host_block`` (the
``log_interval`` drain, the loop's one host sync); moves ``samples_per_s_chip``."""


def read(ctx):
    return 100.0 * ctx["spans"]["span/train/host_block"][0] / ctx["span_s"]
