"""CPU time of the whole process a step, every thread of it, the runtime's
included: the ``cpu_s`` deltas (``getrusage``'s ``ru_utime + ru_stime``) the
program writes on each ``train/host_block`` of the measured span, summed,
over its steps.  Beside ``trainer.loop_cpu_ms`` and ``input.producer_cpu_ms``
it says what the threads outside the program's spans cost.  Moves
``samples_per_s_chip``.  A program without the record reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    drains = (span_window.read(ctx) or {}).get("train/host_block", ())
    cpu = [r.attrs["cpu_s"] for r in drains if "cpu_s" in r.attrs]
    return 1e3 * sum(cpu) / ctx["steps"] if cpu else None
