"""Times a step the kernel took a CPU away from a thread of the process that
still wanted it: the ``nivcsw`` deltas (``getrusage``'s ``ru_nivcsw``) the
program writes on each ``train/host_block`` of the measured span, summed,
over its steps.  A run whose rate fell with this high was held by the
machine, not by the program.  Moves ``samples_per_s_chip``.  A program
without the record reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    drains = (span_window.read(ctx) or {}).get("train/host_block", ())
    switches = [r.attrs["nivcsw"] for r in drains if "nivcsw" in r.attrs]
    return sum(switches) / ctx["steps"] if switches else None
