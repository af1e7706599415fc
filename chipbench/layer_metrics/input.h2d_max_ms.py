"""The longest single ``data/h2d`` (prefetcher: device copy until ready) that
fed a step of the measured span; moves ``samples_per_s_chip``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("data/h2d")
    return 1e3 * max(map(span_window.seconds, spans)) if spans else None
