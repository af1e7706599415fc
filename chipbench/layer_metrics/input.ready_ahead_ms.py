"""The input path's headroom: the median over the measured span's steps of
(start of the step's ``train/data_wait``) - (end of the same step's
``data/h2d``).  Positive: the batch lay ready on the device that long before
the loop asked for it; negative: the loop waited for production.  What
``input.data_wait_pct`` cannot say; moves ``samples_per_s_chip``."""

from chipbench import windows
from chipbench.layer_metrics import span_window


def read(ctx):
    spans = span_window.read(ctx) or {}
    copied = {r.step: r.end_ns for r in spans.get("data/h2d", ())}
    ahead = [(r.start_ns - copied[r.step]) / 1e6
             for r in spans.get("train/data_wait", ()) if r.step in copied]
    return windows.median(ahead) if ahead else None
