"""Mean ``train/metrics_window`` of the measured span: the eager adds that
sum a step's metrics into the window, one dispatch a leaf (attr ``leaves``),
and with them whatever wait a full device queue puts on a dispatch.  Moves
``samples_per_s_chip``.  A program without the span reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("train/metrics_window")
    return 1e3 * sum(map(span_window.seconds, spans)) / len(spans) if spans else None
