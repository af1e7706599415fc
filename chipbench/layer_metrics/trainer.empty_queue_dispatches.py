"""Steps of the measured span dispatched when the step before had already
completed (``train/step`` records with ``device_idle_at_dispatch``): the host
let the device run dry, once for every window drain and once more for every
stall; moves ``samples_per_s_chip``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("train/step")
    if not spans or not any("device_idle_at_dispatch" in r.attrs for r in spans):
        return None
    return sum(1 for r in spans if r.attrs.get("device_idle_at_dispatch"))
