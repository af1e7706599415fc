"""The measured span inside the program's span log, found by step id.

Shared by the readers of the span log; not a metric itself.  The harness
hands a reader histogram totals, not single spans, so these readers take the
records from the program's public ``get_telemetry().span_log()``: one record
a closed span, with ``id``, ``parent_id``, ``name``, ``start_ns``/``end_ns``
(one clock for every thread), ``step`` (the train step the span feeds) and
``attrs``.  Per-layer metrics are read only with ``--trace 1``, where
``(trace_windows + 1) * log_interval`` steps follow the span and ``fit()``
then stops: the span is the ``ctx["steps"]`` steps that end that many before
the last ``train/step`` record.

Nothing is returned, and every reader then reports nothing, unless the log
holds exactly ``ctx["steps"]`` ``train/step`` records there and their
``train/data_wait`` and ``train/host_block`` add up to the histograms' totals
over the span (within 1 ms): log and histograms must mean the same window.
A program without a span log (this benchmark's parent commit) reads as
nothing too."""

#: the harness's histogram totals that the selection has to reproduce
CHECKED = {"train/data_wait": "span/train/data_wait",
           "train/host_block": "span/train/host_block"}
TOLERANCE_S = 1e-3


def seconds(record) -> float:
    return (record.end_ns - record.start_ns) / 1e9


def select(log, ctx):
    """``{span name: [records of the measured span, oldest first]}`` out of
    ``log`` (every record, oldest first), or None where the log does not
    hold the span the histograms in ``ctx`` describe."""
    dispatched = [r.step for r in log if r.name == "train/step" and r.step is not None]
    if not dispatched:
        return None
    mix = ctx["mix"]
    last = max(dispatched) - (int(mix["trace_windows"]) + 1) * int(mix["log_interval"])
    first = last - int(ctx["steps"]) + 1
    by_name: dict = {}
    for r in log:
        if r.step is not None and first <= r.step <= last:
            by_name.setdefault(r.name, []).append(r)
    if first < 1 or len(by_name.get("train/step", ())) != ctx["steps"]:
        return None
    for name, histogram in CHECKED.items():
        total = sum(seconds(r) for r in by_name.get(name, ()))
        if abs(total - ctx["spans"][histogram][0]) > TOLERANCE_S:
            return None
    return by_name


def read(ctx):
    """The measured span's records by name, from the running program."""
    from tpuframe.track.telemetry import get_telemetry

    span_log = getattr(get_telemetry(), "span_log", None)
    return select(span_log(), ctx) if span_log else None
