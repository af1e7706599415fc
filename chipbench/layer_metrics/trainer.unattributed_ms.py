"""The loop's own time: mean over the measured span's ``train/iter`` spans of
the span's duration minus what its child spans cover (pull, dispatch,
callbacks, drain, health fetch, log, checkpoint); moves ``samples_per_s_chip``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = span_window.read(ctx) or {}
    iters = spans.get("train/iter")
    if not iters:
        return None
    covered = dict.fromkeys((r.id for r in iters), 0.0)
    for records in spans.values():
        for r in records:
            if r.parent_id in covered:
                covered[r.parent_id] += span_window.seconds(r)
    own = sum(span_window.seconds(r) - covered[r.id] for r in iters)
    return 1e3 * own / len(iters)
