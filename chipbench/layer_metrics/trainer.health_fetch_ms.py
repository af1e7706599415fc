"""Mean ``train/health_fetch`` of the measured span: the health sentinel's
``device_get`` once a health window, the loop's one host sync besides the
window drain; moves ``step_ms_p90``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("train/health_fetch")
    return 1e3 * sum(map(span_window.seconds, spans)) / len(spans) if spans else None
