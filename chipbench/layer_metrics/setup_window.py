"""The set-up inside the program's span log: everything that closed before
the measured span's first iteration opened.

Shared by the readers that split ``setup_s``; not a metric itself.  The
program leaves one record a phase of every compile request of the process
(``compile/jax_trace``, ``compile/jax_lower``, ``compile/jax_backend``: attrs
``fun``, the program's name, ``self_s``, the phase's seconds less those of the
records nested in it on its thread, and on the backend's ``cache`` = ``hit`` /
``miss`` / ``uncached``), and its own phases as spans: ``setup/state_init``,
``setup/fit_start`` (``fit()`` entry to the loop's first iteration) and, on
the precompile thread, ``compile/precompile_step`` (attr ``used``).
``span_window.select`` finds the measured span's first step; what closed
before its ``train/iter`` opened is the set-up.

Nothing is returned, and every reader then reports nothing, unless the log
and the harness mean the same window: the end of the first ``train/step``
less the start of ``setup/fit_start`` has to agree with the harness's own
``ctx["compile_first_step_s"]`` within ``TOLERANCE_S``.  A program without
the records (this benchmark's parent commit) reads as nothing too."""

from chipbench.layer_metrics import span_window

TOLERANCE_S = 0.25
PHASES = ("compile/jax_trace", "compile/jax_lower", "compile/jax_backend")

seconds = span_window.seconds


def own_seconds(record) -> float:
    """A compile record's seconds less those of the records nested in it, so
    that a sum counts every moment of a thread once."""
    return float(record.attrs.get("self_s", seconds(record)))


def select(log, ctx):
    """``{"records": the set-up's, oldest first, "fit_start": the
    ``setup/fit_start`` record, "first_step": the first ``train/step``}`` out
    of ``log`` (every record, oldest first), or None."""
    span = span_window.select(log, ctx)
    if not span or not span.get("train/iter"):
        return None
    opened = min(r.start_ns for r in span["train/iter"])
    records = [r for r in log if r.end_ns <= opened]
    starts = [r for r in records if r.name == "setup/fit_start"]
    if not starts:
        return None
    fit_start = starts[-1]
    steps = [r for r in records
             if r.name == "train/step" and r.start_ns >= fit_start.end_ns]
    if not steps:
        return None
    first_step = min(steps, key=lambda r: r.start_ns)
    lap_s = (first_step.end_ns - fit_start.start_ns) / 1e9
    if abs(lap_s - ctx["compile_first_step_s"]) > TOLERANCE_S:
        return None
    return {"records": records, "fit_start": fit_start, "first_step": first_step}


def read(ctx):
    """The set-up's records, from the running program."""
    from tpuframe.track.telemetry import get_telemetry

    span_log = getattr(get_telemetry(), "span_log", None)
    return select(span_log(), ctx) if span_log else None


def total(ctx, name, of=seconds, keep=None):
    """``of`` summed over the set-up's ``name`` records, every thread's
    (those that ``keep`` accepts, where given); None where ``read`` is."""
    setup = read(ctx)
    if setup is None:
        return None
    return sum(of(r) for r in setup["records"]
               if r.name == name and (keep is None or keep(r)))


def phase_seconds(ctx, name, caches=None):
    """Summed own seconds of the set-up's ``name`` records (those whose
    ``cache`` attr is in ``caches``, where given)."""
    return total(ctx, name, own_seconds,
                 caches and (lambda r: r.attrs.get("cache") in caches))
