"""The depth of the device's queue at a dispatch: the median over the
measured span's ``train/step`` spans of ``steps_in_flight``, the steps
dispatched before and not yet complete.  At 0 the device waits for the host
(``trainer.empty_queue_dispatches`` counts those); the deeper, the further
the host runs ahead.  Moves ``samples_per_s_chip``.  A program without the
attr reads as nothing."""

from chipbench import windows
from chipbench.layer_metrics import span_window


def read(ctx):
    steps = (span_window.read(ctx) or {}).get("train/step", ())
    depths = [r.attrs["steps_in_flight"] for r in steps if "steps_in_flight" in r.attrs]
    return windows.median(depths) if depths else None
