"""The longest single ``data/assemble`` (loader) that fed a step of the
measured span: a stalled batch shows here whole, where the mean
(``input.assemble_ms``) spreads it over the run; moves ``samples_per_s_chip``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("data/assemble")
    return 1e3 * max(map(span_window.seconds, spans)) if spans else None
