"""The share of the loop thread's time it spent waiting: 100 x (1 - CPU time
over duration) summed over the measured span's ``train/iter`` spans
(``Span.cpu_ns``).  High is a loop the device paces, with room for the host;
near zero is a host that sets the pace.  ``trainer.dispatch_ms``,
``trainer.host_block_pct`` and ``trainer.unattributed_ms`` say where that
wait fell, this says how much of it there is.  Moves ``samples_per_s_chip``.
A program whose spans have no such slot reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    iters = (span_window.read(ctx) or {}).get("train/iter")
    cpu = [getattr(r, "cpu_ns", None) for r in iters or ()]
    lasted = sum(r.end_ns - r.start_ns for r in iters or ())
    if not lasted or None in cpu:
        return None
    return 100.0 * (1.0 - sum(cpu) / lasted)
