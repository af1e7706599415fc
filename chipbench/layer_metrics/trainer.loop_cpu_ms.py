"""The loop thread's work an iteration: mean over the measured span's
``train/iter`` spans of the CPU time their thread burned inside them
(``Span.cpu_ns``, the program's ``time.thread_time_ns()`` pair).  A blocked
thread burns none, so this is what the host does a step, whichever statement
the runtime made it wait in; against the step time it says how far the host
stands from setting the pace.  Moves ``samples_per_s_chip``.  A program whose
spans have no such slot reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    iters = (span_window.read(ctx) or {}).get("train/iter")
    cpu = [getattr(r, "cpu_ns", None) for r in iters or ()]
    if not cpu or None in cpu:
        return None
    return sum(cpu) / len(cpu) / 1e6
